"""Tests for logical-axis -> mesh-axis resolution (launch/sharding.py).

These run on a fake Mesh built from a 1-device CPU backend via
jax.sharding.Mesh over a reshaped device array is impossible here, so we
exercise resolve_spec through a lightweight stand-in mesh object with the
production shapes (the function only reads .shape and .axis_names).
"""
import jax
from jax.sharding import PartitionSpec as P

from repro.launch import sharding as sh
from repro.launch.mesh import make_mesh


class FakeMesh:
    def __init__(self, shape_map):
        self.shape = dict(shape_map)
        self.axis_names = tuple(shape_map)


POD_MESH = FakeMesh({"data": 16, "model": 16})
MULTI_MESH = FakeMesh({"pod": 2, "data": 16, "model": 16})


def test_ff_gets_model_axis():
    spec = sh.resolve_spec(("embed", "ff"), (4096, 14336), POD_MESH)
    assert spec == P("data", "model")  # embed FSDP fallback + ff model


def test_batch_gets_data_axis():
    spec = sh.resolve_spec(("batch", None), (256, 4096), POD_MESH)
    assert spec == P("data", None)


def test_batch_gets_pod_and_data_on_multipod():
    spec = sh.resolve_spec(("batch", None), (256, 4096), MULTI_MESH)
    assert spec == P(("pod", "data"), None)


def test_indivisible_dim_not_sharded():
    # 40 heads % 16 != 0 -> heads cannot take the model axis; head_dim 128 can.
    spec = sh.resolve_spec(
        ("embed", "heads", "head_dim"), (5120, 40, 128), POD_MESH
    )
    assert spec[1] is None
    assert spec[2] == "model"


def test_mesh_axis_used_at_most_once():
    spec = sh.resolve_spec(("ff", "vocab"), (65536, 65536), POD_MESH)
    axes = [s for s in spec if s is not None]
    assert len(axes) == len(set(axes))
    assert "model" in axes


def test_priority_prefers_ff_over_vocab():
    spec = sh.resolve_spec(("vocab", "ff"), (151936, 17408), POD_MESH)
    assert spec == P(None, "model") or spec == P("data", "model")
    assert spec[1] == "model"


def test_none_logical_is_replicated():
    assert sh.resolve_spec(None, (7, 3), POD_MESH) == P()


def test_parameters_never_take_pod_axis():
    """Params are replicated across pods (pure DP over `pod`)."""
    for logical, shape in [
        (("embed", "ff"), (4096, 14336)),
        (("vocab", "embed"), (128256, 4096)),
        (("kv_heads", "head_dim"), (8, 128)),
    ]:
        spec = sh.resolve_spec(logical, shape, MULTI_MESH)
        flat = [a for s in spec if s is not None for a in (s if isinstance(s, tuple) else (s,))]
        assert "pod" not in flat, (logical, spec)


def test_experts_shardable():
    spec = sh.resolve_spec(("experts", "embed", "ff"), (64, 2048, 1408), POD_MESH)
    # ff=1408=16*88 divisible -> model on ff; experts stays unsharded then.
    assert spec[2] == "model" or spec[0] == "model"


def test_batch_shardings_on_real_mesh():
    mesh = make_mesh((1, 1), ("data", "model"))
    specs = {"tokens": jax.ShapeDtypeStruct((8, 128), jax.numpy.int32)}
    out = sh.batch_shardings(specs, mesh)
    assert out["tokens"].spec == P("data", None)
