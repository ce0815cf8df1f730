"""Sharding rules: batch/activation/param spec selection by divisibility.

Spec construction only — no device mesh is required until a spec is applied,
so these run fast on a single-device interpreter. pipeline_par's numerical
equivalence is covered by test_apps_and_pipeline (subprocess, 4 devices).
"""
import dataclasses

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.dist.pipeline_par import split_stages
from repro.dist.sharding import ShardingRules, make_rules
from repro.launch.mesh import make_debug_mesh


@pytest.fixture
def mesh():
    # a 1-device mesh still carries named axes of size 1; for spec-selection
    # tests we need real sizes, so fake them via a 1x1 mesh + explicit rules
    return make_debug_mesh(1, 1)


class _FakeMesh:
    """Just enough Mesh surface for spec selection (shape + axis_names)."""

    def __init__(self, shape):
        self.shape = dict(shape)
        self.axis_names = tuple(shape)


def _rules(dp=4, tp=2, **kw):
    return ShardingRules(mesh=_FakeMesh({"data": dp, "model": tp}),
                         dp_axes=("data",), **kw)


def test_make_rules_partitions_axes(mesh):
    rules = make_rules(mesh)
    assert rules.dp_axes == ("data",)
    assert rules.tp_axis == "model"
    assert rules.dp_size == 1 and rules.tp_size == 1


def test_batch_spec_divisibility():
    rules = _rules(dp=4)
    assert rules.batch_spec("train", 64, 4096) == P(("data",))
    # batch not divisible -> the sequence dim takes the data axes
    assert rules.batch_spec("prefill", 2, 4096) == P(None, ("data",))
    # decode never seq-shards its (B, 1) tokens
    assert rules.batch_spec("decode", 2, 4096) == P()
    # seq_shard preference flips the order
    seq_rules = dataclasses.replace(rules, seq_shard=True)
    assert seq_rules.batch_spec("prefill", 64, 4096) == P(None, ("data",))


def test_batch_spec_no_dp_axes():
    rules = dataclasses.replace(_rules(), dp_axes=())
    assert rules.batch_spec("train", 64, 4096) == P()


def test_param_spec_shards_one_model_dim():
    rules = _rules(tp=4)
    assert rules._param_spec((1024, 512)) == P(None, "model")
    # odd last dim falls back to an earlier divisible dim
    assert rules._param_spec((1024, 513)) == P("model", None)
    # scanned stacks never shard the layer dim
    assert rules._param_spec((32, 513, 515)) == P(None, None, None)
    assert rules._param_spec((32, 512, 513)) == P(None, "model", None)
    # tp=1 -> fully replicated
    assert _rules(tp=1)._param_spec((1024, 512)) == P(None, None)


def test_params_shardings_tree_alignment(mesh):
    rules = make_rules(mesh)
    shapes = {"embed": jax.ShapeDtypeStruct((128, 64), np.float32),
              "layers": {"w": jax.ShapeDtypeStruct((4, 64, 64), np.float32)}}
    shardings = rules.params_shardings(shapes)
    assert set(shardings) == {"embed", "layers"}
    assert shardings["embed"].mesh == mesh


def test_split_stages_shapes_and_divisibility():
    params = {"w": np.zeros((8, 16, 16))}
    staged = split_stages(params, 4)
    assert staged["w"].shape == (4, 2, 16, 16)
    with pytest.raises(ValueError):
        split_stages({"w": np.zeros((9, 4))}, 4)
