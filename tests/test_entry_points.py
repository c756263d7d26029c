"""The five entry points say their defaults in their signatures.

Until PR 45 eighteen keywords of ``amp.initialize``, ``DistributedDataParallel``,
``_DistributedFused``, ``DistributedFusedAdam`` and ``ZeRO3FusedAdam`` defaulted
to a sentinel and took their value from a dict inside the constructor, through
the tuner's ``resolve_trainer_knobs``. The values below are those dicts', written
out: an object built with no keyword holds what it held then.
"""

import inspect

import jax.numpy as jnp
import pytest

from beforeholiday_tpu import amp
from beforeholiday_tpu.optimizers.distributed_fused import (
    DistributedFusedAdam, _DistributedFused)
from beforeholiday_tpu.optimizers.zero3 import ZeRO3FusedAdam
from beforeholiday_tpu.parallel.distributed import DistributedDataParallel

_ENTRY_POINTS = {
    "amp.initialize": amp.initialize,
    "DistributedDataParallel": DistributedDataParallel,
    "_DistributedFused": _DistributedFused,
    "DistributedFusedAdam": DistributedFusedAdam,
    "ZeRO3FusedAdam": ZeRO3FusedAdam,
}
_COLLECTIVE_KNOBS = {"bucket_bytes": None, "compress": False, "overlap_backward": False,
                     "hierarchical": False}
# what ``resolve_trainer_knobs`` gave each knob at the parent with ``tuned=False``
_RESOLVED = {
    "DistributedDataParallel": _COLLECTIVE_KNOBS,
    "_DistributedFused": _COLLECTIVE_KNOBS,
    "DistributedFusedAdam": _COLLECTIVE_KNOBS,
    "ZeRO3FusedAdam": {**_COLLECTIVE_KNOBS, "bucket_bytes": 4 * 1024 * 1024, "prefetch": 1},
}
_PLAIN = (type(None), bool, int, float, str, tuple)


@pytest.mark.parametrize("name", sorted(_ENTRY_POINTS))
def test_the_signature_has_no_tuner_keyword_and_no_sentinel_default(name):
    parameters = inspect.signature(_ENTRY_POINTS[name]).parameters
    assert not {"tuned", "tuning_key", "tuning_manifest"} & set(parameters)
    for p in parameters.values():
        if p.default is inspect.Parameter.empty:
            continue
        # a value a reader can use: a constant, or a dtype (``wire_dtype=jnp.bfloat16``)
        assert isinstance(p.default, _PLAIN) or jnp.issubdtype(p.default, jnp.floating), \
            f"{name}({p.name}={p.default!r})"


@pytest.mark.parametrize("name,knob", [(n, k) for n in sorted(_RESOLVED) for k in _RESOLVED[n]])
def test_an_object_built_with_no_keyword_holds_the_knob_at_its_resolved_default(name, knob):
    want = _RESOLVED[name][knob]
    assert inspect.signature(_ENTRY_POINTS[name]).parameters[knob].default == want
    got = getattr(_ENTRY_POINTS[name](), knob)
    assert got == want and type(got) is type(want), (got, want)


def test_amp_initialize_without_an_opt_level_is_o5():
    params = {"w": jnp.ones((4, 4)), "norm": jnp.ones((4,))}
    model = amp.initialize(lambda p, x: x @ p["w"], params)
    assert model.policy == amp.opt_levels["O5"]
    assert model.params["w"].dtype == jnp.bfloat16 and model.params["norm"].dtype == jnp.float32
