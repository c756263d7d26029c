"""Tests of the benchmark itself: ``pytest benchmark/tests`` (tier-1's ``tests/`` is separate).

They run on the CPU, on four virtual devices so that the data-parallel fixture
has its mesh; nothing here gives a time."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
