"""shardlib property tests, the trainer's parameter shardings, and
elastic mesh planning."""
import jax
import pytest
pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.common import shardlib
from repro.configs.base import DLRMConfig
from repro.models import dlrm
from repro.train.elastic import ElasticCoordinator, viable_mesh_shape
from repro.train.optim import make_optimizer, opt_logical_axes


class FakeMesh:
    def __init__(self, shape: dict):
        self.shape = shape
        self.axis_names = tuple(shape)


MESH = FakeMesh({"data": 16, "model": 16})
MESH3 = FakeMesh({"pod": 2, "data": 16, "model": 16})


@settings(max_examples=60, deadline=None)
@given(dims=st.lists(st.integers(1, 4096), min_size=1, max_size=4),
       names=st.lists(st.sampled_from(
           ["batch", "vocab", "mlp", "fsdp", "heads", None]),
           min_size=1, max_size=4))
def test_sanitized_pspec_always_divisible(dims, names):
    n = min(len(dims), len(names))
    dims, names = dims[:n], names[:n]
    rules = shardlib.make_rules()
    for mesh in (MESH, MESH3):
        spec = shardlib.sanitized_pspec(dims, names, rules, mesh)
        used = []
        for dim, axis in zip(dims, tuple(spec) + (None,) * 10):
            if axis is None:
                continue
            axes = (axis,) if isinstance(axis, str) else axis
            prod = 1
            for a in axes:
                prod *= mesh.shape[a]
                used.append(a)
            assert dim % prod == 0       # never uneven
        assert len(used) == len(set(used))  # each mesh axis at most once


def test_pod_axis_filtered_on_single_pod():
    rules = shardlib.make_rules()
    spec = shardlib.sanitized_pspec((256, 128), ("batch", None), rules, MESH)
    assert spec == P("data", None)
    spec3 = shardlib.sanitized_pspec((256, 128), ("batch", None), rules,
                                     MESH3)
    assert spec3 == P(("pod", "data"), None)


def test_overrides_apply():
    rules = shardlib.make_rules({"heads": None, "head_dim": "model"})
    spec = shardlib.sanitized_pspec((512, 9, 64), ("fsdp", "heads",
                                                   "head_dim"), rules, MESH)
    assert spec == P("data", None, "model")


STACKED = DLRMConfig(name="dlrm-tiny", n_sparse=4, n_dense=13, embed_dim=16,
                     vocab_sizes=(512,) * 4, bottom_mlp=(32, 16),
                     top_mlp=(32, 1), multi_hot=1)
PACKED = DLRMConfig(name="dcnv2-tiny", n_sparse=4, n_dense=5, embed_dim=8,
                    vocab_sizes=(50, 7, 300, 3), hot_sizes=(3, 1, 5, 2),
                    bottom_mlp=(16, 8), top_mlp=(32, 16, 1),
                    interaction="dcn", dcn_layers=2, dcn_rank=4)


def test_abstract_init_allocates_nothing_and_returns_the_logical_tree():
    for cfg in (STACKED, PACKED):
        init = lambda: dlrm.init_params(jax.random.PRNGKey(0), cfg)
        abs_params, logical = shardlib.abstract_init(init)
        params, want_logical = init()
        leaves = jax.tree_util.tree_leaves(abs_params)
        assert leaves and all(type(x) is jax.ShapeDtypeStruct
                              for x in leaves)
        assert logical == want_logical, cfg.name
        assert jax.tree_util.tree_structure(abs_params) == \
            jax.tree_util.tree_structure(params)
        for a, p in zip(leaves, jax.tree_util.tree_leaves(params)):
            assert (a.shape, a.dtype) == (p.shape, p.dtype)


def test_shardings_for_matches_sanitized_pspec_per_leaf():
    """The trainer's params and row-wise Adagrad state on the host mesh:
    one NamedSharding a leaf, each `sanitized_pspec` of the leaf's shape
    and logical axes under the config's rules."""
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         (jax.sharding.AxisType.Auto,) * 2,
                         devices=jax.devices()[:1])
    cfg = STACKED
    rules = shardlib.make_rules(dict(cfg.sharding_overrides))
    abs_params, logical = shardlib.abstract_init(
        lambda: dlrm.init_params(jax.random.PRNGKey(0), cfg))
    opt = make_optimizer("rowwise_adagrad", lr=0.02)
    abs_opt = jax.eval_shape(opt.init, abs_params)
    opt_logical = opt_logical_axes("rowwise_adagrad", logical,
                                   params=abs_params)
    got = {}
    for name, tree, lg_tree in (("params", abs_params, logical),
                                ("opt", abs_opt, opt_logical)):
        got[name] = shardlib.shardings_for(tree, lg_tree, rules, mesh)
        flat, treedef = jax.tree_util.tree_flatten(tree)
        assert jax.tree_util.tree_structure(got[name]) == treedef
        for leaf, lg, sh in zip(flat, treedef.flatten_up_to(lg_tree),
                                jax.tree_util.tree_leaves(got[name])):
            assert isinstance(sh, NamedSharding) and sh.mesh == mesh
            assert sh.spec == shardlib.sanitized_pspec(leaf.shape, lg,
                                                       rules, mesh)
    # the tables' rows over "model"
    assert got["params"]["tables"].spec == P(None, "model", None)


@settings(max_examples=40, deadline=None)
@given(n=st.integers(1, 2048))
def test_viable_mesh_shape_fits(n):
    dp, tp = viable_mesh_shape(n, model_parallel=16)
    assert dp * tp <= n or (dp == 1 and tp <= 16)
    assert dp & (dp - 1) == 0            # power of two
    assert tp <= 16


def test_elastic_coordinator_plans():
    coord = ElasticCoordinator(n_devices=256, model_parallel=16)
    assert coord.current == (16, 16)
    plan = coord.recovery_plan(200)      # lost 56 devices
    assert plan["mesh_shape"][0] * plan["mesh_shape"][1] <= 200
    plan = coord.recovery_plan(8)        # catastrophic loss
    assert plan["mesh_shape"][1] <= 8
