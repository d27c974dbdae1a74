"""Per-kernel shape/dtype sweeps, assert_allclose against ref.py oracles
(assignment requirement: every Pallas kernel validated in interpret mode)."""
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("v,d,b,bag", [
    (64, 8, 4, 1), (512, 32, 16, 4), (1024, 128, 32, 8), (128, 10, 8, 3),
])
def test_embedding_bag_sweep(v, d, b, bag, dtype):
    rng = np.random.RandomState(v + d)
    table = jnp.asarray(rng.randn(v, d), dtype)
    ids = jnp.asarray(rng.randint(0, v, (b, bag)), jnp.int32)
    for combiner in ("sum", "mean"):
        out = ops.embedding_bag(table, ids, combiner=combiner,
                                interpret=True)
        exp = ref.embedding_bag_ref(table, ids, combiner=combiner)
        tol = 1e-6 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(exp, np.float32),
            rtol=tol, atol=tol)


@settings(max_examples=20, deadline=None)
@given(v=st.integers(8, 300), d=st.sampled_from([4, 16, 33]),
       b=st.integers(1, 24), bag=st.integers(1, 6))
def test_embedding_bag_property(v, d, b, bag):
    rng = np.random.RandomState(v * 31 + d)
    table = jnp.asarray(rng.randn(v, d), jnp.float32)
    ids = jnp.asarray(rng.randint(0, v, (b, bag)), jnp.int32)
    out = ops.embedding_bag(table, ids, interpret=True)
    exp = ref.embedding_bag_ref(table, ids)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,f,d,tile", [
    (64, 27, 16, 32), (128, 27, 128, 128), (32, 8, 8, 8), (48, 13, 32, 16),
])
def test_dot_interact_sweep(b, f, d, tile, dtype):
    rng = np.random.RandomState(b + f)
    feats = jnp.asarray(rng.randn(b, f, d), dtype)
    out = ops.dot_interact(feats, tile_b=tile, interpret=True)
    exp = ref.dot_interact_ref(feats)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(exp, np.float32),
        rtol=tol, atol=tol)
    assert out.shape == (b, f * (f - 1) // 2)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,f,d,h,tile", [
    (64, 15, 64, 128, 32), (128, 10, 602, 128, 64), (32, 25, 32, 16, 32),
])
def test_sage_aggregate_sweep(b, f, d, h, tile, dtype):
    rng = np.random.RandomState(b)
    neigh = jnp.asarray(rng.randn(b, f, d), dtype)
    w = jnp.asarray(rng.randn(d, h) * d ** -0.5, dtype)
    out = ops.sage_aggregate(neigh, w, tile_b=tile, interpret=True)
    exp = ref.sage_aggregate_ref(neigh, w)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(exp, np.float32),
        rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("v,d,b,bag", [
    (64, 8, 4, 1), (512, 32, 16, 4), (1024, 128, 32, 8), (128, 10, 8, 3),
])
def test_embedding_bag_fused_parity(v, d, b, bag, dtype):
    """The fused perf variant is BIT-IDENTICAL to the baseline (same
    j-ascending f32 accumulation), and allclose to the ref oracle."""
    rng = np.random.RandomState(v + d)
    table = jnp.asarray(rng.randn(v, d), dtype)
    ids = jnp.asarray(rng.randint(0, v, (b, bag)), jnp.int32)
    for combiner in ("sum", "mean"):
        base = ops.embedding_bag(table, ids, combiner=combiner,
                                 interpret=True)
        fused = ops.embedding_bag_fused(table, ids, combiner=combiner,
                                        interpret=True)
        assert bool(jnp.all(base == fused)), (v, d, b, bag, combiner)
        exp = ref.embedding_bag_ref(table, ids, combiner=combiner)
        tol = 1e-6 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(
            np.asarray(fused, np.float32), np.asarray(exp, np.float32),
            rtol=tol, atol=tol)


def test_embedding_bag_fused_fallbacks():
    """Over the VMEM table budget or the bag unroll bound, the fused
    entry point must fall back to the row-DMA baseline (same numbers)."""
    from repro.kernels import embedding_bag as eb
    rng = np.random.RandomState(0)
    # bag over the unroll bound (small table)
    table = jnp.asarray(rng.randn(64, 8), jnp.float32)
    big_bag = jnp.asarray(rng.randint(0, 64, (4, eb._FUSED_MAX_BAG + 1)),
                          jnp.int32)
    out = ops.embedding_bag_fused(table, big_bag, interpret=True)
    assert bool(jnp.all(out == ops.embedding_bag(table, big_bag,
                                                 interpret=True)))
    # table over the VMEM budget (small bag)
    v = eb._FUSED_MAX_TABLE_BYTES // (2 * 4) + 8
    big_table = jnp.asarray(rng.randn(v, 2), jnp.float32)
    ids = jnp.asarray(rng.randint(0, v, (4, 2)), jnp.int32)
    out = ops.embedding_bag_fused(big_table, ids, interpret=True)
    assert bool(jnp.all(out == ops.embedding_bag(big_table, ids,
                                                 interpret=True)))


@pytest.mark.parametrize("call", [
    lambda: ops.embedding_bag(jnp.zeros((64, 128)),
                              jnp.zeros((8, 1), jnp.int32)),
    lambda: ops.embedding_bag_fused(jnp.zeros((64, 128)),
                                    jnp.zeros((8, 1), jnp.int32)),
    lambda: ops.dot_interact(jnp.zeros((128, 27, 128))),
    lambda: ops.sage_aggregate(jnp.zeros((128, 8, 128)),
                               jnp.zeros((128, 128))),
], ids=["embedding_bag", "embedding_bag_fused", "dot_interact",
        "sage_aggregate"])
def test_compiled_kernel_call_raises_off_tpu(call):
    """The default is the compiled kernel: off the chip a call without
    interpret=True fails loudly instead of quietly interpreting."""
    import jax
    if jax.default_backend() == "tpu":     # pragma: no cover - chip host
        pytest.skip("compiled kernels run on this backend")
    with pytest.raises(Exception, match="interpret"):
        call()


def test_kernels_match_model_code():
    """The kernels' oracles ARE the model-code ops they accelerate."""
    from repro.models.dlrm import dot_interaction
    rng = np.random.RandomState(0)
    feats = jnp.asarray(rng.randn(32, 27, 16), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(dot_interaction(feats)),
        np.asarray(ops.dot_interact(feats, tile_b=32, interpret=True)),
        rtol=1e-5, atol=1e-5)
    from repro.models.embedding import embedding_bag as model_bag
    table = jnp.asarray(rng.randn(128, 16), jnp.float32)
    ids = jnp.asarray(rng.randint(0, 128, (8, 4)), jnp.int32)
    np.testing.assert_allclose(
        np.asarray(model_bag(table, ids)),
        np.asarray(ops.embedding_bag(table, ids, interpret=True)),
        rtol=1e-5, atol=1e-5)
