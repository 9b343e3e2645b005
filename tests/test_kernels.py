"""Kernel validation: Pallas (interpret=True) and chunked-jnp ops vs the
pure-jnp oracles in kernels/ref.py, swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd_scan

rng = np.random.default_rng(42)


def rnd(*shape, dt=jnp.float32, scale=1.0):
    return jnp.asarray(rng.normal(size=shape) * scale, dt)


def tol(dt):
    return 2e-2 if dt == jnp.bfloat16 else 2e-5


# ----------------------------------------------------------- flash attention
@pytest.mark.parametrize("B,S,H,K,D", [
    (1, 128, 4, 4, 32),     # MHA
    (2, 256, 8, 2, 64),     # GQA 4:1
    (1, 256, 4, 1, 64),     # MQA
    (1, 512, 2, 2, 128),    # MXU-aligned head dim
    (1, 288, 14, 2, 64),    # GQA 7:1, a block that is no power of two
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(B, S, H, K, D, dtype, causal):
    q, k, v = rnd(B, S, H, D, dt=dtype), rnd(B, S, K, D, dt=dtype), \
        rnd(B, S, K, D, dt=dtype)
    o = flash_attention(q, k, v, causal=causal, block_q=64, block_kv=128,
                        interpret=True)
    o2 = ref.naive_attention(q, k, v, causal=causal)
    err = jnp.abs(o.astype(jnp.float32) - o2.astype(jnp.float32)).max()
    assert float(err) < tol(dtype) * 10, float(err)
    assert o.dtype == q.dtype


def test_flash_attention_uneven_blocks():
    q, k, v = rnd(1, 192, 2, 32), rnd(1, 192, 1, 32), rnd(1, 192, 1, 32)
    o = flash_attention(q, k, v, causal=True, block_q=64, block_kv=64,
                        interpret=True)
    o2 = ref.naive_attention(q, k, v, causal=True)
    assert float(jnp.abs(o - o2).max()) < 1e-4


# ------------------------------------------------- chunked-jnp attention path
@pytest.mark.parametrize("S,block_q", [(512, 128), (1024, 128), (2048, 256)])
def test_binary_causal_attention(S, block_q):
    q, k, v = rnd(2, S, 4, 32), rnd(2, S, 2, 32), rnd(2, S, 2, 32)
    o = ops.attention(q, k, v, causal=True, block_q=block_q, block_kv=256)
    o2 = ref.naive_attention(q, k, v, causal=True)
    assert float(jnp.abs(o - o2).max()) < 1e-4


@pytest.mark.parametrize("valid", [1, 37, 100])
def test_decode_attention_valid_len(valid):
    q = rnd(2, 1, 8, 32)
    k, v = rnd(2, 128, 4, 32), rnd(2, 128, 4, 32)
    o = ops.attention(q, k, v, causal=False, kv_valid_len=jnp.asarray(valid))
    o2 = ref.naive_attention(q, k, v, kv_valid_len=jnp.asarray(valid))
    assert float(jnp.abs(o - o2).max()) < 1e-5


def test_cross_attention_matches():
    q = rnd(2, 64, 4, 32)
    k, v = rnd(2, 96, 4, 32), rnd(2, 96, 4, 32)
    o = ops.attention(q, k, v, causal=False, block_kv=32)
    o2 = ref.naive_attention(q, k, v, causal=False)
    assert float(jnp.abs(o - o2).max()) < 1e-5


# -------------------------------------------------------------------- SSD
@pytest.mark.parametrize("s,chunk", [(128, 32), (256, 64), (512, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_kernel_sweep(s, chunk, dtype):
    b, h, p, n = 2, 2, 16, 8
    x = rnd(b, s, h, p, dt=dtype)
    dt = jnp.abs(rnd(b, s, h, scale=0.1)).astype(jnp.float32)
    A = -jnp.abs(rnd(h))
    Bm, Cm = rnd(b, s, n, dt=dtype), rnd(b, s, n, dt=dtype)
    Dp = rnd(h)
    y = ssd_scan(x, dt, A, Bm, Cm, Dp, chunk=chunk, interpret=True)
    y2 = ref.naive_ssd(x, dt, A, Bm, Cm, Dp)
    scale = float(jnp.abs(y2.astype(jnp.float32)).max()) + 1e-6
    err = float(jnp.abs(y.astype(jnp.float32) - y2.astype(jnp.float32)).max())
    assert err / scale < tol(dtype), (err, scale)


def test_ssd_jnp_matches_kernel_semantics():
    b, s, h, p, n = 1, 256, 2, 8, 4
    x = rnd(b, s, h, p)
    dt = jnp.abs(rnd(b, s, h, scale=0.1))
    A = -jnp.abs(rnd(h))
    Bm, Cm, Dp = rnd(b, s, n), rnd(b, s, n), rnd(h)
    y1 = ops.ssd_scan(x, dt, A, Bm, Cm, Dp, chunk=64)
    y2 = ssd_scan(x, dt, A, Bm, Cm, Dp, chunk=64, interpret=True)
    assert float(jnp.abs(y1 - y2).max()) < 1e-4


def test_ssd_decode_step_consistent():
    b, s, h, p, n = 1, 16, 2, 8, 4
    x = rnd(b, s, h, p)
    dt = jnp.abs(rnd(b, s, h, scale=0.1))
    A = -jnp.abs(rnd(h))
    Bm, Cm, Dp = rnd(b, s, n), rnd(b, s, n), rnd(h)
    y_ref = ref.naive_ssd(x, dt, A, Bm, Cm, Dp)
    st = jnp.zeros((b, h, p, n))
    for t in range(s):
        st, yt = ops.ssd_step(st, x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t], Dp)
        assert float(jnp.abs(yt - y_ref[:, t]).max()) < 1e-4


# -------------------------------------------------------------------- mLSTM
@pytest.mark.parametrize("s,chunk", [(128, 32), (256, 64)])
def test_mlstm_chunked(s, chunk):
    b, h, d = 2, 2, 16
    q, k, v = rnd(b, s, h, d), rnd(b, s, h, d, scale=0.5), rnd(b, s, h, d)
    ig, fg = rnd(b, s, h), rnd(b, s, h) + 2.0
    y = ops.mlstm_scan(q, k, v, ig, fg, chunk=chunk)
    y2 = ref.naive_mlstm(q, k, v, ig, fg)
    scale = float(jnp.abs(y2).max()) + 1e-6
    assert float(jnp.abs(y - y2).max()) / scale < 1e-4


# ------------------------------------------------------------- flash decode
@pytest.mark.parametrize("B,S,H,K,D,vl", [
    (2, 256, 8, 2, 64, 100),   # GQA, partial cache
    (1, 512, 4, 4, 32, 512),   # MHA, full cache
    (2, 128, 4, 1, 32, 1),     # MQA, single valid token
    (1, 256, 8, 8, 128, 37),   # MXU-aligned head dim
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_sweep(B, S, H, K, D, vl, dtype):
    from repro.kernels.flash_decode import flash_decode
    q = rnd(B, 1, H, D, dt=dtype)
    k, v = rnd(B, S, K, D, dt=dtype), rnd(B, S, K, D, dt=dtype)
    o = flash_decode(q, k, v, jnp.asarray(vl), block_kv=64, interpret=True)
    o2 = ref.naive_attention(q, k, v, kv_valid_len=jnp.asarray(vl))
    err = jnp.abs(o.astype(jnp.float32) - o2.astype(jnp.float32)).max()
    assert float(err) < tol(dtype) * 10
    assert o.dtype == q.dtype


# ------------------------------------------ gradients and left-padding masks
def test_flash_attention_grad_matches_reference():
    q, k, v = rnd(2, 128, 4, 32), rnd(2, 128, 2, 32), rnd(2, 128, 2, 32)

    def loss(attn):
        return lambda q, k, v: jnp.sum(attn(q, k, v) ** 2)
    g = jax.grad(loss(lambda q, k, v: flash_attention(
        q, k, v, block_q=32, block_kv=64, interpret=True)),
        argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss(lambda q, k, v: ref.naive_attention(q, k, v)),
                     argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, g_ref):
        assert float(jnp.abs(a - b).max()) < 1e-4


@pytest.mark.parametrize("S,block_q,block_kv,dtype", [
    (288, 96, 96, jnp.float32),      # three chunks of 96, one diagonal each
    (288, 96, 48, jnp.float32),      # chunks of 48: two cross each diagonal
    (288, 96, 144, jnp.bfloat16),    # a chunk wider than the q block
])
def test_flash_attention_grad_chunks(S, block_q, block_kv, dtype):
    """Forward and gradient at GQA 7:1, d_head 64, over several kv
    chunks whose size is not a power of two."""
    q, k, v = rnd(1, S, 14, 64, dt=dtype), rnd(1, S, 2, 64, dt=dtype), \
        rnd(1, S, 2, 64, dt=dtype)
    ct = rnd(1, S, 14, 64)

    def loss(attn):
        return lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32) * ct)
    flash = lambda q, k, v: flash_attention(              # noqa: E731
        q, k, v, block_q=block_q, block_kv=block_kv, interpret=True)
    o = flash(q, k, v).astype(jnp.float32)
    o_ref = ref.naive_attention(q, k, v).astype(jnp.float32)
    assert float(jnp.abs(o - o_ref).max()) < tol(dtype) * 10
    g = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss(ref.naive_attention), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, g_ref):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        scale = float(jnp.abs(b).max())
        assert float(jnp.abs(a - b).max()) < tol(dtype) * 10 * scale


@pytest.mark.parametrize("start", [40, 96, 150, 192])
def test_flash_attention_kv_start_chunks(start):
    """A kv_start inside a chunk of 96 (40, 150) and on a chunk's edge
    (96, 192): the real rows' outputs and gradients equal attention over
    the unpadded sequence; the pad rows give zeros and take no
    gradient."""
    S, H, K, D = 288, 14, 2, 64
    q, k, v = rnd(2, S, H, D), rnd(2, S, K, D), rnd(2, S, K, D)
    ct = rnd(2, S, H, D)
    starts = jnp.asarray([0, start], jnp.int32)

    def flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, starts, block_q=96,
                                       block_kv=96, interpret=True)[1, start:]
                       * ct[1, start:])

    def naive(q, k, v):
        return jnp.sum(ref.naive_attention(q, k, v)[0] * ct[1, start:])
    o = flash_attention(q, k, v, starts, block_q=96, block_kv=96,
                        interpret=True)
    o_ref = ref.naive_attention(q[1:, start:], k[1:, start:], v[1:, start:])
    assert float(jnp.abs(o[1, start:] - o_ref[0]).max()) < 1e-5
    assert float(jnp.abs(o[1, :start]).max()) == 0.0
    assert float(jnp.abs(o[0] - ref.naive_attention(q[:1], k[:1], v[:1])[0]
                         ).max()) < 1e-5
    g = jax.grad(flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(naive, argnums=(0, 1, 2))(q[1:, start:], k[1:, start:],
                                               v[1:, start:])
    for a, b in zip(g, g_ref):
        assert float(jnp.abs(a[1, start:] - b[0]).max()) < 1e-4
        assert float(jnp.abs(a[1, :start]).max()) == 0.0
        assert float(jnp.abs(a[0]).max()) == 0.0


def test_flash_attention_long_padded():
    """A long sequence (eight q blocks of 512, four chunks of 1,024, 20
    live pairs a head) whose kv_start lies past its first chunk and cuts
    the second: the real rows' outputs and gradients equal attention
    over the unpadded sequence."""
    S, D, start = 4096, 128, 1100
    q, k, v = rnd(1, S, 2, D), rnd(1, S, 1, D), rnd(1, S, 1, D)
    starts = jnp.asarray([start], jnp.int32)
    ct = rnd(1, S, 2, D)

    def flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, starts, interpret=True)
                       [0, start:] * ct[0, start:])

    def naive(q, k, v):
        return jnp.sum(ref.naive_attention(q, k, v)[0] * ct[0, start:])
    o = flash_attention(q, k, v, starts, interpret=True)
    o_ref = ref.naive_attention(q[:, start:], k[:, start:], v[:, start:])
    assert float(jnp.abs(o[0, start:] - o_ref[0]).max()) < 1e-4
    g = jax.grad(flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(naive, argnums=(0, 1, 2))(q[:, start:], k[:, start:],
                                               v[:, start:])
    for a, b in zip(g, g_ref):
        assert float(jnp.abs(a[0, start:] - b[0]).max()) < 1e-4


@pytest.mark.parametrize("start", [0, 150])
def test_flash_attention_mla_widths(start):
    """Latent attention's widths, q/k 192 and v 128 (as many kv heads as
    q heads): forward and gradient equal attention over the unpadded
    sequence, with kv_start zero and inside the second chunk of 96."""
    S, H, D, Dv = 288, 4, 192, 128
    q, k, v = rnd(2, S, H, D), rnd(2, S, H, D), rnd(2, S, H, Dv)
    ct = rnd(2, S, H, Dv)
    starts = jnp.asarray([0, start], jnp.int32)
    scale = D ** -0.5 * 1.3     # not a power of two, as MLA's is not

    def attend(q, k, v):
        return flash_attention(q, k, v, starts, scale=scale, block_q=96,
                               block_kv=96, interpret=True)

    def naive(q, k, v):
        return ref.naive_attention(q, k, v, scale=scale)
    o = attend(q, k, v)
    assert o.shape == (2, S, H, Dv)
    for b in (0, 1):
        s0 = int(starts[b])
        o_ref = naive(q[b:b + 1, s0:], k[b:b + 1, s0:], v[b:b + 1, s0:])
        assert float(jnp.abs(o[b, s0:] - o_ref[0]).max()) < 1e-5
    g = jax.grad(lambda q, k, v: jnp.sum(attend(q, k, v)[1, start:]
                                         * ct[1, start:]),
                 argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(lambda q, k, v: jnp.sum(naive(q, k, v)[0]
                                             * ct[1, start:]),
                     argnums=(0, 1, 2))(q[1:, start:], k[1:, start:],
                                        v[1:, start:])
    for a, b in zip(g, g_ref):
        assert float(jnp.abs(a[1, start:] - b[0]).max()) < 1e-4
        assert float(jnp.sum(jnp.abs(a[1, :start]))) == 0.0
        assert float(jnp.abs(a[0]).max()) == 0.0


def test_flash_attention_mla_dead_first_chunk():
    """q/k 192, v 128 over four q blocks of 256 and two chunks of 512,
    with kv_start past the first chunk: that chunk is dead and skipped;
    the real rows' outputs and gradients equal attention over the
    unpadded sequence."""
    S, D, Dv, start = 1024, 192, 128, 600
    q, k, v = rnd(1, S, 2, D), rnd(1, S, 2, D), rnd(1, S, 2, Dv)
    starts = jnp.asarray([start], jnp.int32)
    ct = rnd(1, S, 2, Dv)

    def flash(q, k, v):
        return flash_attention(q, k, v, starts, block_q=256, block_kv=512,
                               interpret=True)
    o = flash(q, k, v)
    o_ref = ref.naive_attention(q[:, start:], k[:, start:], v[:, start:])
    assert float(jnp.abs(o[0, start:] - o_ref[0]).max()) < 1e-4
    g = jax.grad(lambda q, k, v: jnp.sum(flash(q, k, v)[0, start:]
                                         * ct[0, start:]),
                 argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(lambda q, k, v: jnp.sum(
        ref.naive_attention(q, k, v)[0] * ct[0, start:]),
        argnums=(0, 1, 2))(q[:, start:], k[:, start:], v[:, start:])
    for a, b in zip(g, g_ref):
        assert a.shape[-1] == b.shape[-1]
        assert float(jnp.abs(a[0, start:] - b[0]).max()) < 1e-4


@pytest.mark.parametrize("B,S,block_q,block_kv,live,masked", [
    (1, 2304, 512, 1024, 12, 6),     # train_steady: 6 q blocks, 3 chunks
    (2, 288, 96, 96, 6, 3),          # 3 q blocks, 3 chunks of the same size
    (1, 288, 96, 48, 12, 6),         # 3 q blocks, 6 chunks of half the size
])
def test_flash_attention_chunk_counters(B, S, block_q, block_kv, live, masked):
    """kernels.attn_chunks / kernels.attn_chunks_masked count the live
    (q block, kv chunk) pairs of a traced call and those the diagonal
    crosses: ``live`` and ``masked`` per head and batch row."""
    from repro import telemetry
    H, K, D = 14, 2, 64
    jax.clear_caches()                       # trace afresh: counts once
    before = telemetry.counters()
    jax.eval_shape(lambda q, k, v: flash_attention(
        q, k, v, block_q=block_q, block_kv=block_kv, interpret=True),
        jax.ShapeDtypeStruct((B, S, H, D), jnp.bfloat16),
        jax.ShapeDtypeStruct((B, S, K, D), jnp.bfloat16),
        jax.ShapeDtypeStruct((B, S, K, D), jnp.bfloat16))
    after = telemetry.counters()
    delta = {c: after.get(c, 0) - before.get(c, 0)
             for c in ("kernels.attn_chunks", "kernels.attn_chunks_masked")}
    assert delta == {"kernels.attn_chunks": B * H * live,
                     "kernels.attn_chunks_masked": B * H * masked}


@pytest.mark.parametrize("impl", ["pallas", "jnp"])
def test_left_padding_is_masked(impl):
    """Row 1 is padded by 40 slots: its real rows must equal attention
    over the unpadded sequence, in prefill and in decode."""
    B, S, H, K, D = 2, 128, 4, 2, 32
    q, k, v = rnd(B, S, H, D), rnd(B, S, K, D), rnd(B, S, K, D)
    start = jnp.asarray([0, 40], jnp.int32)
    if impl == "pallas":
        o = flash_attention(q, k, v, start, block_q=32, block_kv=64,
                            interpret=True)
    else:
        o = ops.attention(q, k, v, kv_start=start, block_q=32, block_kv=64)
    o_ref = ref.naive_attention(q[1:, 40:], k[1:, 40:], v[1:, 40:])
    assert float(jnp.abs(o[1, 40:] - o_ref[0]).max()) < 1e-5
    assert float(jnp.abs(o[1, :40]).max()) == 0.0        # pad rows: zeros
    from repro.kernels.flash_decode import flash_decode
    q1 = rnd(B, 1, H, D)
    if impl == "pallas":
        od = flash_decode(q1, k, v, jnp.asarray(100), start, block_kv=32,
                          interpret=True)
    else:
        od = ops.attention(q1, k, v, causal=False,
                           kv_valid_len=jnp.asarray(100), kv_start=start)
    od_ref = ref.naive_attention(q1[1:], k[1:, 40:], v[1:, 40:],
                                 kv_valid_len=jnp.asarray(60))
    assert float(jnp.abs(od[1] - od_ref[0]).max()) < 1e-5


def test_fit_block_and_padded_len():
    assert ops.fit_block(2304, 512) == 384      # vlm: 2048 text + 256 patches
    assert ops.fit_block(64, 512) == 64
    assert ops.fit_block(63, 512) == 0          # no kernel block: jnp path
    assert ops.padded_len(63) == 64 and ops.padded_len(8) == 16
    assert ops.fit_block(ops.padded_len(37), 1024) == 48
