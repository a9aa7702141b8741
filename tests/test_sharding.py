"""Unit tests for the sharding/spec layer (no multi-device needed)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.archs import tiny_version
from repro.configs.base import get_config
from repro.parallel import specs as SP
from repro.parallel.sharding import DEFAULT_RULES, axis_rules, resolve_spec


def _mesh11():
    return jax.make_mesh((1, 1), ("data", "model"))


def test_resolve_spec_drops_missing_axes():
    mesh = _mesh11()
    spec = resolve_spec(("batch", "seq", "heads"), mesh=mesh)
    # "pod" missing from mesh → dropped from the batch tuple
    assert spec == P("data", None, "model")


def test_sanitize_drops_nondivisible():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    # fake a 4-wide model axis via a mesh dict? use real check on axis size 1:
    spec = SP.sanitize_spec(P(None, "model"), (8, 7), mesh)
    assert spec == P(None, "model")  # axis size 1 divides everything


def test_param_specs_rank_consistency():
    from repro.models import api
    mesh = _mesh11()
    for arch in ["tinyllama-1.1b", "mamba2-130m", "jamba-v0.1-52b",
                 "whisper-medium", "moonshot-v1-16b-a3b"]:
        cfg = tiny_version(get_config(arch))
        shapes = jax.eval_shape(lambda: api.init(jax.random.key(0), cfg))
        spec_tree = SP.param_specs(shapes, mesh, cfg=cfg, kind="train")
        flat_specs = jax.tree.leaves(spec_tree,
                                     is_leaf=lambda s: isinstance(s, P))
        flat_shapes = jax.tree.leaves(shapes)
        assert len(flat_specs) == len(flat_shapes)
        for spec, sds in zip(flat_specs, flat_shapes):
            assert len(spec) <= len(sds.shape), (spec, sds.shape)


def test_zero1_no_duplicate_axes():
    mesh = _mesh11()
    sds = jax.ShapeDtypeStruct((16, 32), jnp.float32)
    spec = P("data", None)
    out = SP.zero1_specs(spec, sds, mesh, axis="data")
    used = [a for a in out if a is not None]
    assert len(used) == len(set(used))


def test_attention_kv_fallbacks():
    """kv_heads % model != 0 must not shard wk/wv by head."""
    import re
    from repro.models import api
    cfg = get_config("grok-1-314b").with_(n_layers=2)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    shapes = jax.eval_shape(lambda: api.init(jax.random.key(0), cfg))
    for kind in ("train", "decode"):
        spec_tree = SP.param_specs(shapes, mesh, cfg=cfg, kind=kind)
        flat = jax.tree_util.tree_flatten_with_path(
            spec_tree, is_leaf=lambda s: isinstance(s, P))[0]
        for path, spec in flat:
            ps = SP._path_str(path)
            if re.search(r"(wk|wv)$", ps):
                # head dim (-2) never sharded for grok (kv=8 vs model axis)
                dims = list(spec)
                if len(dims) >= 2:
                    assert dims[-2] is None or dims[-2] != "model"


def test_cache_specs_cover_all_families():
    from repro.configs.base import SHAPES
    from repro.launch import steps as ST
    mesh = _mesh11()
    for arch in ["tinyllama-1.1b", "mamba2-130m", "jamba-v0.1-52b",
                 "whisper-medium"]:
        cfg = get_config(arch).with_(n_layers=get_config(arch).attn_period or 2)
        with axis_rules(dict(DEFAULT_RULES), mesh):
            cs = ST.cache_specs(cfg, SHAPES["decode_32k"], mesh)
        assert jax.tree.leaves(cs,
                               is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def test_make_rules_seq_shard_for_long_context():
    from repro.configs.base import SHAPES
    from repro.launch import steps as ST
    cfg = get_config("mamba2-130m")
    mesh = jax.sharding.AbstractMesh((1, 4, 1), ("pod", "data", "model"))
    rules = ST.make_rules(cfg, SHAPES["long_500k"], mesh)
    assert rules["batch"] is None           # batch 1 can't fill DP
    assert rules["seq_shard"] == "data"     # SP takes the axis instead
