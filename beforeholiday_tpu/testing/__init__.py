"""In-repo reference models, fault injectors, drills and chip checks.

The reference ships complete GPT/BERT model definitions inside the library for
its distributed tests (ref: apex/transformer/testing/standalone_gpt.py:111,
standalone_bert.py:255, standalone_transformer_lm.py:1574). This package plays
the same role, and nothing outside it but the tests, ``benchmark/`` and the
examples imports it (``tests/test_layering.py``):

* models — ``gpt``, ``bert``, ``moe_model``, ``_model_utils`` (the benchmark's
  GPT cells train ``gpt``);
* ``faults`` — deterministic fault injectors for the guard and elastic tests;
* ``drills`` — the kill/resume, chaos and goodput drills, each against a
  bitwise or exact-sum oracle;
* ``tpu_checks`` — kernel and step checks that only mean something on a chip.
"""

from beforeholiday_tpu.testing import faults  # noqa: F401
from beforeholiday_tpu.testing import gpt  # noqa: F401
