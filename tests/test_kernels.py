"""Per-kernel validation: shape/dtype sweeps, interpret mode vs ref oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import striped as st
from repro.kernels import ops, ref


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,s,h,kvh,d,bq,bk",
    [
        (1, 128, 4, 4, 64, 64, 64),  # MHA
        (2, 256, 8, 2, 64, 128, 128),  # GQA
        (2, 192, 6, 2, 32, 64, 64),  # non-pow2 heads, odd blocks
        (1, 128, 4, 1, 128, 128, 32),  # MQA
    ],
)
def test_striped_attention_kernel_sweep(dtype, b, s, h, kvh, d, bq, bk):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = _rand(ks[0], (b, s, h, d), dtype)
    k = _rand(ks[1], (b, s, kvh, d), dtype)
    v = _rand(ks[2], (b, s, kvh, d), dtype)
    pos = st.striped_positions(s, 4)
    out_k = ops.attention(q, k, v, pos, pos, causal=True,
                          impl="interpret", block_q=bq, block_k=bk)
    out_r = ops.attention(q, k, v, pos, pos, causal=True, impl="xla")
    np.testing.assert_allclose(
        np.asarray(out_k, np.float32), np.asarray(out_r, np.float32),
        atol=TOL[dtype], rtol=TOL[dtype],
    )


@pytest.mark.parametrize("window", [None, 64])
@pytest.mark.parametrize("causal", [True, False])
def test_striped_attention_masks(window, causal):
    b, s, h, kvh, d = 2, 128, 4, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k, v = (_rand(ks[i], (b, s, h if i == 0 else kvh, d), jnp.float32)
               for i in range(3))
    pos = st.striped_positions(s, 8)
    out_k = ops.attention(q, k, v, pos, pos, causal=causal, window=window,
                          impl="interpret", block_q=32, block_k=32)
    out_r = ops.attention(q, k, v, pos, pos, causal=causal, window=window,
                          impl="xla")
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), atol=2e-5)


def test_striped_attention_softcap():
    b, s, h, d = 1, 64, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q, k, v = (_rand(ks[i], (b, s, h, d), jnp.float32) for i in range(3))
    pos = jnp.arange(s)
    out_k = ops.attention(q, k, v, pos, pos, softcap=20.0, impl="interpret",
                          block_q=32, block_k=32)
    out_r = ops.attention(q, k, v, pos, pos, softcap=20.0, impl="xla")
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,s,h,kvh,d,bk,off,win",
    [
        (2, 128, 4, 4, 64, 64, 0, None),
        (4, 256, 8, 2, 64, 128, 0, None),
        (2, 128, 4, 2, 32, 32, 128, None),  # offset shard
        (2, 256, 8, 2, 64, 64, 0, 64),  # SWA
        (1, 64, 4, 1, 128, 64, 64, 32),  # MQA + offset + window
    ],
)
def test_flash_decode_kernel_sweep(dtype, b, s, h, kvh, d, bk, off, win):
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = _rand(ks[0], (b, 1, h, d), dtype)
    k = _rand(ks[1], (b, s, kvh, d), dtype)
    v = _rand(ks[2], (b, s, kvh, d), dtype)
    lens = jnp.asarray(
        np.random.default_rng(0).integers(0, off + s + 1, b), jnp.int32
    )
    pk = ops.decode_partial(q, k, v, lens, k_pos_offset=off, window=win,
                            impl="interpret", block_k=bk)
    pr = ops.decode_partial(q, k, v, lens, k_pos_offset=off, window=win,
                            impl="xla")
    np.testing.assert_allclose(
        np.asarray(pk.o), np.asarray(pr.o), atol=5e-2 if dtype == jnp.bfloat16 else 1e-4
    )
    np.testing.assert_allclose(
        np.nan_to_num(np.asarray(pk.m), neginf=-1e9),
        np.nan_to_num(np.asarray(pr.m), neginf=-1e9), atol=1e-2,
    )
    np.testing.assert_allclose(np.asarray(pk.l), np.asarray(pr.l),
                               rtol=2e-2, atol=1e-4)


@pytest.mark.parametrize("window", [1, 2, 32, 64])
def test_window_convention_parity(window):
    """Cross-kernel sliding-window convention at the boundary: a query at
    global position qp attends keys with 0 <= qp - kp < window (self
    inclusive).  The prefill kernel applies it literally; the decode kernel
    sees the cache WITHOUT the query's own KV (query position == lengths) and
    merges the own-token partial — both must select the identical window."""
    from repro.models import attention as A

    b, s, h, kvh, d = 2, 64, 4, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = _rand(ks[0], (b, s, h, d), jnp.float32)
    k = _rand(ks[1], (b, s, kvh, d), jnp.float32)
    v = _rand(ks[2], (b, s, kvh, d), jnp.float32)
    pos = jnp.arange(s)
    # prefill convention: last row of the striped kernel output
    full = ops.attention(q, k, v, pos, pos, causal=True, window=window,
                         impl="interpret", block_q=32, block_k=32)
    last_prefill = np.asarray(full)[:, -1]
    # decode convention: cache = tokens 0..s-2, query's own KV merged apart
    qd = q[:, s - 1 : s]
    lens = jnp.full((b,), s - 1, jnp.int32)
    p_hist = ops.decode_partial(qd, k[:, : s - 1], v[:, : s - 1], lens,
                                window=window, impl="interpret", block_k=21)
    p_own = A.partial_attention(qd, k[:, s - 1 :], v[:, s - 1 :], None)
    last_decode = np.asarray(
        A.finalize_partial(A.merge_partial(p_hist, p_own))
    )[:, 0]
    np.testing.assert_allclose(last_decode, last_prefill, atol=2e-5)


def test_decode_partials_compose_to_full():
    """Sharded decode partials (kernel) merged across shards == full attn."""
    from repro.models import attention as A

    b, s, h, kvh, d = 2, 256, 4, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = _rand(ks[0], (b, 1, h, d), jnp.float32)
    k = _rand(ks[1], (b, s, kvh, d), jnp.float32)
    v = _rand(ks[2], (b, s, kvh, d), jnp.float32)
    lens = jnp.asarray([100, 256], jnp.int32)
    parts = []
    n_shards = 4
    per = s // n_shards
    for i in range(n_shards):
        sl = slice(i * per, (i + 1) * per)
        parts.append(
            ops.decode_partial(q, k[:, sl], v[:, sl], lens,
                               k_pos_offset=i * per, impl="interpret",
                               block_k=32)
        )
    combined = A.combine_partials(parts)
    ref_out = A.decode_attention(q, k, v, lens)
    np.testing.assert_allclose(
        np.asarray(combined, np.float32), np.asarray(ref_out, np.float32),
        atol=2e-5,
    )


@pytest.mark.parametrize("env,want", [(None, None), ("interpret", "interpret")])
def test_default_impl_follows_backend(monkeypatch, env, want):
    """With no REPRO_KERNEL_IMPL the first dispatch picks the backend's
    impl (Pallas on a TPU, XLA elsewhere); the variable overrides it."""
    if env is None:
        monkeypatch.delenv("REPRO_KERNEL_IMPL", raising=False)
        want = "pallas" if jax.default_backend() == "tpu" else "xla"
    else:
        monkeypatch.setenv("REPRO_KERNEL_IMPL", env)
    monkeypatch.setattr(ops, "_DEFAULT_IMPL", None)
    assert ops.get_default_impl() == want


def test_bad_impl_env_raises_at_first_dispatch(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "cuda")
    monkeypatch.setattr(ops, "_DEFAULT_IMPL", None)
    with pytest.raises(ValueError, match="REPRO_KERNEL_IMPL"):
        ops.get_default_impl()
