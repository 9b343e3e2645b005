"""Compile the live path's kernels for a described TPU v5e, without a chip.

The TPU compiler refuses what interpret mode accepts (block shapes off the
tiling, too much VMEM, a kernel XLA cannot partition), so the kernels of
the main path are compiled here at real widths: the Pallas attention
kernels at internvl2-1b widths (14 query / 2 KV heads, d_head 64) and at
deepseek-v2-lite's (16 heads, q/k 192, v 128), its grouped expert
products, and the vmapped scheduler decision kernels at the lane width of
a Theta-scale sweep, in both replay dtypes.  Nothing runs: a pass says
the chip's compiler accepts the program, not that it is correct or fast.

The topology is described inside a fixture, never at import, so every
test worker collects the same tests and only the worker that runs this
file loads the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import decision_jax as dj
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_decode import flash_decode

H, K, D = 14, 2, 64            # internvl2-1b attention widths


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these tests
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _sds(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("B,S", [(1, 2048),   # text alone
                                 (1, 2304),   # 2048 text + 256 patches
                                 (4, 64),     # a padded short prompt batch
                                 (8, 2048),   # serving's largest prefill
                                 (1, 16384)])  # 272 block pairs a head
def test_flash_attention_compiles(one_chip, B, S):
    q, kv = _sds(one_chip, (B, S, H, D)), _sds(one_chip, (B, S, K, D))
    start = _sds(one_chip, (B,), jnp.int32)
    fwd = jax.jit(lambda q, k, v, s: flash_attention(q, k, v, s))
    assert "tpu_custom_call" in fwd.lower(q, kv, kv, start).compile().as_text()
    grad = jax.jit(jax.grad(
        lambda q, k, v, s: jnp.sum(
            flash_attention(q, k, v, s).astype(jnp.float32)),
        argnums=(0, 1, 2)))
    grad.lower(q, kv, kv, start).compile()


def test_flash_attention_mla_compiles(one_chip):
    """deepseek-v2-lite's training shape: a microbatch of two 8,192-token
    sequences, 16 heads, q/k 192 and v 128."""
    B, S, H, D, Dv = 2, 8192, 16, 192, 128
    q, v = _sds(one_chip, (B, S, H, D)), _sds(one_chip, (B, S, H, Dv))
    start = _sds(one_chip, (B,), jnp.int32)
    fwd = jax.jit(lambda q, k, v, s: flash_attention(q, k, v, s))
    assert "tpu_custom_call" in fwd.lower(q, q, v, start).compile().as_text()
    grad = jax.jit(jax.grad(
        lambda q, k, v, s: jnp.sum(
            flash_attention(q, k, v, s).astype(jnp.float32)),
        argnums=(0, 1, 2)))
    grad.lower(q, q, v, start).compile()


@pytest.mark.parametrize("T,k,E", [(16384, 6, 8), (8, 6, 64)])
def test_moe_grouped_products_compile(one_chip, T, k, E):
    """The expert layer's grouped products at deepseek-v2-lite's widths
    over its static bound of rows (T x min(top-k, held), one tile per
    held expert), for a training microbatch on 8 held experts (256-row
    tiles) and a decode step of 8 tokens on all 64 (16-row tiles): x W1,
    h W2, the transposed products of the backward and the weights'
    gradient."""
    from repro.kernels import moe_gmm, ops
    d, F = 2048, 1408
    tm = ops.gmm_tile(T * min(k, E), E)
    M = -(-(T * min(k, E) + E * (tm - 1)) // tm) * tm
    counts = _sds(one_chip, (E,), jnp.int32)
    for K, N in ((d, F), (F, d)):
        lhs, rhs = _sds(one_chip, (M, K)), _sds(one_chip, (E, K, N))
        dout = _sds(one_chip, (M, N))
        c = jax.jit(lambda x, w, n: moe_gmm.gmm(x, w, n, tm=tm)).lower(
            lhs, rhs, counts).compile()
        assert "moe_gmm" in c.as_text()
        jax.jit(lambda g, w, n: moe_gmm.gmm(g, w, n, tm=tm, transpose=True)
                ).lower(dout, rhs, counts).compile()
        c = jax.jit(lambda x, g, n: moe_gmm.tgmm(x, g, n, tm=tm, n_groups=E)
                    ).lower(lhs, dout, counts).compile()
        assert "moe_tgmm" in c.as_text()


def test_flash_decode_compiles(one_chip):
    B, S = 4, 4096
    c = jax.jit(lambda q, k, v, n, s: flash_decode(q, k, v, n, s)).lower(
        _sds(one_chip, (B, 1, H, D)), _sds(one_chip, (B, S, K, D)),
        _sds(one_chip, (B, S, K, D)), _sds(one_chip, (), jnp.int32),
        _sds(one_chip, (B,), jnp.int32)).compile()
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("dtype", ["float64", "float32"])
@pytest.mark.parametrize("kernel", ["easy_shadow", "victims", "apportion"])
def test_decision_kernels_compile(one_chip, kernel, dtype):
    N, P = 256, 32             # calls x lanes of a Theta-scale capture
    with jax.enable_x64(dtype == "float64"):
        f, i = dj._dtypes(dtype)
        lanes = lambda dt: _sds(one_chip, (N, P), dt)      # noqa: E731
        calls = lambda dt: _sds(one_chip, (N,), dt)        # noqa: E731
        mask = lanes(jnp.bool_)
        fn, args = {
            "easy_shadow": (dj._easy_shadow_kernel,
                            (calls(i), calls(i), lanes(f), lanes(i), mask,
                             calls(f))),
            "victims": (dj._victims_kernel,
                        (lanes(i), lanes(f), mask, calls(i))),
            "apportion": (dj._apportion_kernel,
                          (lanes(i), lanes(i), mask, calls(i))),
        }[kernel]
        jax.jit(jax.vmap(fn)).lower(*args).compile()
