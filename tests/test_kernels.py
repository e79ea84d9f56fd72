"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles,
executed with interpret=True on CPU (TPU is the lowering target)."""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.flash_attention import flash_attention
from repro.kernels.next_event import next_event, next_event_ref
from repro.kernels.ops import attention_op, next_event_op, wkv6_op
from repro.kernels.ref import attention_ref, wkv6_ref
from repro.kernels.rwkv6_scan import wkv6

RNG = jax.random.PRNGKey(7)


def _tol(dt):
    return 2e-2 if dt == jnp.bfloat16 else 1e-4


@pytest.mark.parametrize("B,H,K,S,hd", [
    (2, 4, 2, 256, 64),      # GQA
    (1, 8, 8, 128, 128),     # MHA, MXU-square head
    (2, 4, 1, 256, 64),      # MQA
    (1, 2, 2, 384, 64),      # ragged block count
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(B, H, K, S, hd, dtype, causal):
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (B, H, S, hd), dtype)
    k = jax.random.normal(ks[1], (B, K, S, hd), dtype)
    v = jax.random.normal(ks[2], (B, K, S, hd), dtype)
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    ref = attention_ref(q, k, v, causal=causal)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                - ref.astype(jnp.float32))))
    assert err < _tol(dtype), err


def test_flash_attention_block_shape_invariance():
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (1, 2, 512, 64))
    k = jax.random.normal(ks[1], (1, 2, 512, 64))
    v = jax.random.normal(ks[2], (1, 2, 512, 64))
    o1 = flash_attention(q, k, v, block_q=128, block_k=128, interpret=True)
    o2 = flash_attention(q, k, v, block_q=256, block_k=64, interpret=True)
    assert float(jnp.max(jnp.abs(o1 - o2))) < 1e-5


@pytest.mark.parametrize("B,H,S,N", [(2, 4, 256, 64), (1, 2, 128, 32),
                                     (2, 2, 192, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_wkv6_sweep(B, H, S, N, dtype):
    ks = jax.random.split(RNG, 5)
    r = jax.random.normal(ks[0], (B, H, S, N), dtype) * 0.5
    k = jax.random.normal(ks[1], (B, H, S, N), dtype) * 0.5
    v = jax.random.normal(ks[2], (B, H, S, N), dtype) * 0.5
    logw = -jnp.exp(jax.random.normal(ks[3], (B, H, S, N)) * 0.5 - 2.0)
    u = jax.random.normal(ks[4], (H, N)) * 0.1
    y, st = wkv6(r, k, v, logw.astype(jnp.float32),
                 u.astype(jnp.float32), chunk=64, interpret=True)
    yr, sr = wkv6_ref(r, k, v, logw, u)
    assert float(jnp.max(jnp.abs(y.astype(jnp.float32)
                                 - yr.astype(jnp.float32)))) < _tol(dtype)
    assert float(jnp.max(jnp.abs(st - sr))) < 1e-4


def test_ops_layout_adapters():
    """ops.py wrappers accept the model's [B,S,H,N] layout."""
    ks = jax.random.split(RNG, 5)
    q = jax.random.normal(ks[0], (2, 128, 4, 64))
    k = jax.random.normal(ks[1], (2, 128, 2, 64))
    v = jax.random.normal(ks[2], (2, 128, 2, 64))
    out = attention_op(q, k, v, causal=True, interpret=True)
    assert out.shape == q.shape
    ref = attention_ref(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                        v.transpose(0, 2, 1, 3), causal=True)
    assert float(jnp.max(jnp.abs(out.transpose(0, 2, 1, 3) - ref))) < 1e-4

    r = jax.random.normal(ks[3], (2, 128, 2, 32)) * 0.5
    logw = -jnp.exp(jax.random.normal(ks[4], (2, 128, 2, 32)) * 0.3 - 2.0)
    u = jnp.zeros((2, 32))
    y, st = wkv6_op(r, r, r, logw, u, interpret=True)
    assert y.shape == r.shape and st.shape == (2, 2, 32, 32)


@pytest.mark.parametrize("shape", [(7,), (512,), (513,), (3, 1000), (2, 2, 65)])
def test_next_event_matches_oracle(shape):
    """Fused masked min/argmin == the two-reduction jnp oracle, including
    ragged sizes that exercise the inf padding."""
    t = jax.random.uniform(RNG, shape) * 1e6
    v, i = next_event(t, interpret=True)
    vr, ir = next_event_ref(t)
    assert jnp.array_equal(v, vr) and jnp.array_equal(i, ir)


def test_next_event_mask_and_ties():
    t = jnp.array([[5.0, 1.0, 1.0, 9.0]])
    v, i = next_event(t, interpret=True)
    assert float(v[0]) == 1.0 and int(i[0]) == 1   # first occurrence on ties
    mask = jnp.array([[True, False, False, True]])
    v, i = next_event(t, mask, interpret=True)
    assert float(v[0]) == 5.0 and int(i[0]) == 0
    # ties across block boundaries keep the lowest index
    t2 = jnp.full((1, 1200), 3.0)
    v2, i2 = next_event(t2, block=256, interpret=True)
    assert int(i2[0]) == 0


def test_next_event_all_masked_matches_argmin_convention():
    t = jnp.ones((2, 8))
    mask = jnp.zeros((2, 8), bool)
    v, i = next_event(t, mask, interpret=True)
    vr, ir = next_event_ref(t, mask)
    assert jnp.all(jnp.isinf(v)) and jnp.array_equal(i, ir)


@pytest.mark.parametrize("shape,rows", [
    ((4096, 8), None),       # wide-sweep shape: auto row tiling kicks in
    ((1000, 3), None),       # ragged rows → +inf row padding
    ((100, 6), 7),           # explicit rows_per_block, non-dividing
    ((5, 2048), None),       # M > block: one row per program, M tiled
    ((1, 1), 16),            # rows_per_block clamped to R
])
def test_next_event_row_tiling(shape, rows):
    """The (rows_per_block, block) tiling — auto-picked from the input
    shape or explicit — must not change any result: same values, same
    first-occurrence tie indices, padded rows sliced off."""
    t = jax.random.uniform(RNG, shape) * 1e3
    # Duplicate minima across the row-tile boundary exercise tie-breaking
    # under the widened accumulators.
    t = t.at[..., 0].set(0.5).at[..., -1].set(0.5)
    mask = jax.random.uniform(jax.random.fold_in(RNG, 1), shape) > 0.2
    v, i = next_event(t, mask, rows_per_block=rows, interpret=True)
    vr, ir = next_event_ref(t, mask)
    assert jnp.array_equal(v, vr) and jnp.array_equal(i, ir)


def test_next_event_auto_rows_heuristic():
    """Auto tiling targets ~block elements per program: many rows when M
    is small, the TPU's minimum of 8 rows when M fills the tile, and
    always a multiple of 8 or the whole R."""
    from repro.kernels.next_event import DEFAULT_BLOCK, _auto_rows
    assert _auto_rows(4096, 8, DEFAULT_BLOCK) == DEFAULT_BLOCK // 8
    assert _auto_rows(4096, DEFAULT_BLOCK, DEFAULT_BLOCK) == 8
    assert _auto_rows(4096, 24, DEFAULT_BLOCK) == 24     # 21 → next 8-multiple
    assert _auto_rows(2, 8, DEFAULT_BLOCK) == 2          # clamped to R
    assert _auto_rows(0, 8, DEFAULT_BLOCK) == 1          # degenerate floor


def test_next_event_f64_and_vmap():
    """The engine paths run the kernel under x64 (bit-exact scheduler) and
    under vmap (batched fleet sweeps)."""
    from repro.core.vec_engine import x64
    with x64():
        t = jnp.asarray(jax.random.uniform(RNG, (3, 50)), jnp.float64)
        v, i = next_event_op(t, interpret=True)
        assert v.dtype == jnp.float64
        assert jnp.array_equal(v, jnp.min(t, axis=-1))
    tb = jax.random.uniform(RNG, (4, 33))
    v_b, i_b = jax.vmap(lambda row: next_event(row, interpret=True))(tb)
    assert jnp.array_equal(v_b, jnp.min(tb, axis=-1))
    assert jnp.array_equal(i_b, jnp.argmin(tb, axis=-1).astype(jnp.int32))


def test_kernel_matches_model_xla_path():
    """Pallas wkv6 == the model's XLA chunked path (same math)."""
    import numpy as np
    from repro.models.rwkv6 import _wkv_chunked
    ks = jax.random.split(RNG, 4)
    B, H, S, N = 2, 2, 128, 32
    shape = (B, S, H, N)                        # model layout
    r = jax.random.normal(ks[0], shape) * 0.5
    k = jax.random.normal(ks[1], shape) * 0.5
    v = jax.random.normal(ks[2], shape) * 0.5
    logw = -jnp.exp(jax.random.normal(ks[3], shape) * 0.3 - 2.0)
    u = jnp.zeros((H, N))
    y_x, st_x = _wkv_chunked(r, k, v, logw, u,
                             jnp.zeros((B, H, N, N)), 64)
    y_p, st_p = wkv6_op(r, k, v, logw, u, interpret=True)
    assert float(jnp.max(jnp.abs(y_x - y_p.astype(jnp.float32)))) < 1e-4
    assert float(jnp.max(jnp.abs(st_x - st_p))) < 1e-4
