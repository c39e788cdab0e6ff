"""TPU v5e compile checks of the main-path Pallas kernels at lwm-7b widths,
and of the prefill kernels at glm4-9b's too (16-head GQA groups, where the
shape-chosen tiles need the most VMEM).

Each case compiles for a described (not attached) ``v5e:2x2`` topology, so
Mosaic's block-shape and VMEM rules are enforced at real widths without a
chip; interpret-mode parity lives in the kernel test files.  The topology
is described inside a fixture, after collection, and every case skips
where it cannot be described.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config

CFG = get_config("lwm-7b")
H, KVH, D = CFG.n_heads, CFG.n_kv_heads, CFG.head_dim
# (H, KVH, D) of the configurations the prefill kernels are compiled at
WIDTHS = {name: (c.n_heads, c.n_kv_heads, c.head_dim)
          for name, c in ((n, get_config(n)) for n in ("lwm-7b", "glm4-9b"))}
PREFILL_CASES = [("lwm-7b", 2048), ("glm4-9b", 2048), ("glm4-9b", 16384)]


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.asarray(topo.devices).reshape(4), ("data",))


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A described-topology compile is written to the persistent cache but
    cannot be read back without a chip: keep the cache off around them."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("page_size", [1, 16])
@pytest.mark.parametrize("window", [None, 4096])
def test_paged_decode_compiles(one_chip, no_persistent_cache, page_size,
                               window):
    """Paged decode over the pool's float32 mirror: the default page size
    (1) and the chip smoke's (16), with and without window masking."""
    from repro.kernels import ops

    b, n_pages = 8, 8192 // page_size
    max_pages = 2048 // page_size
    s = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731

    def fn(q, kp, vp, table, lengths, pos, qpos):
        return ops.paged_decode_partial(
            q, kp, vp, table, lengths, pos, query_pos=qpos, window=window,
            impl="pallas",
        ).o

    pages = s((n_pages, page_size, KVH, D), jnp.float32)
    _compile(
        fn, s((b, 1, H, D), jnp.bfloat16), pages, pages,
        s((b, max_pages), jnp.int32), s((b,), jnp.int32),
        s((n_pages, page_size), jnp.int32), s((b,), jnp.int32),
    )


@pytest.mark.parametrize("widths,t", PREFILL_CASES)
def test_packed_prefill_compiles(one_chip, no_persistent_cache, widths, t):
    """Packed ragged prefill in the model's bf16, at the tile the kernel
    chooses for the shape."""
    from repro.kernels import ops

    H, KVH, D = WIDTHS[widths]  # noqa: N806
    s = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    _compile(
        lambda q, k, v, off: ops.prefill_packed(q, k, v, off, impl="pallas"),
        s((t, H, D), jnp.bfloat16), s((t, KVH, D), jnp.bfloat16),
        s((t, KVH, D), jnp.bfloat16), s((9,), jnp.int32),
    )


@pytest.mark.parametrize("widths,tl,dtype", [
    pytest.param("lwm-7b", 1024, jnp.bfloat16, id="lwm-7b-1024"),
    pytest.param("glm4-9b", 2048, jnp.bfloat16, id="glm4-9b-2048"),
    pytest.param("glm4-9b", 16384, jnp.bfloat16, id="glm4-9b-16384"),
    pytest.param("lwm-7b", 1024, jnp.float32, id="lwm-7b-1024-float32"),
    pytest.param("glm4-9b", 16384, jnp.float32, id="glm4-9b-16384-float32"),
])
def test_ring_chunk_compiles(one_chip, no_persistent_cache, widths, tl, dtype):
    """One ring step of a DoP-2 group with its float32 carried state, at
    the tile the kernel chooses for the shard: q/k/v in the model's bf16,
    and in float32 (the four-chip smoke's weights), whose wider blocks
    change the chosen tile and its VMEM limit."""
    from repro.kernels import ops

    H, KVH, D = WIDTHS[widths]  # noqa: N806
    s = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731

    def fn(q, k, v, qo, ko, o, m, l):
        return ops.prefill_ring_chunk(
            q, k, v, qo, ko, (o, m, l), q_shard=1, k_shard=0, n_shards=2,
            impl="pallas",
        )

    _compile(
        fn, s((tl, H, D), dtype), s((tl, KVH, D), dtype),
        s((tl, KVH, D), dtype), s((9,), jnp.int32),
        s((9,), jnp.int32), s((tl, H, D), jnp.float32),
        s((tl, H), jnp.float32), s((tl, H), jnp.float32),
    )


def _switched_ring_compiles(mesh4, widths, tl, dtype):
    """Compile `esp.switched_ring_chunk` in shard_map over 4 chips, with
    ``tl`` tokens a rank."""
    from repro.core import esp
    from repro.core.shmap import shmap

    H, KVH, D = WIDTHS[widths]  # noqa: N806
    n = 4
    sh = NamedSharding(mesh4, P("data"))

    def body(q, k, v, off):
        o, m, l = esp.switched_ring_chunk(
            "data", n, 1, q, k, v, off, None, impl="pallas",
        )
        return o / jnp.where(l == 0.0, 1.0, l)[..., None]

    fn = shmap(
        body, mesh4,
        in_specs=(P("data"), P("data"), P("data"), P(None)),
        out_specs=P("data"),
    )
    _compile(
        fn, _spec(sh, (n * tl, H, D), dtype),
        _spec(sh, (n * tl, KVH, D), dtype),
        _spec(sh, (n * tl, KVH, D), dtype),
        _spec(NamedSharding(mesh4, P(None)), (9,), jnp.int32),
    )


def test_switched_ring_chunk_compiles_on_mesh(mesh4, no_persistent_cache):
    """`esp.switched_ring_chunk` inside shard_map over 4 chips: every
    rank-specialized branch of the `lax.switch` lowers to the kernel."""
    _switched_ring_compiles(mesh4, "lwm-7b", 512, jnp.bfloat16)


@pytest.mark.parametrize("widths,tl", [("lwm-7b", 512), ("glm4-9b", 16384)])
def test_switched_ring_chunk_compiles_on_mesh_float32(
        mesh4, no_persistent_cache, widths, tl):
    """The same in float32, as the four-chip smoke runs it: the wider
    blocks change the chosen tile and its VMEM limit."""
    _switched_ring_compiles(mesh4, widths, tl, jnp.float32)


def test_switched_paged_partial_compiles_on_mesh(mesh4, no_persistent_cache):
    """`esp._switched_paged_partial` inside shard_map over 4 chips, each
    rank reading its own pool mirror with 16-token pages."""
    from repro.core import esp
    from repro.core.shmap import shmap

    n, b, page, n_pages, max_pages = 4, 8, 16, 512, 64
    sh = NamedSharding(mesh4, P("data"))
    rep = NamedSharding(mesh4, P(None))

    def body(q, kp, vp, table, lengths):
        part = esp._switched_paged_partial(
            "data", n, q, kp[0], vp[0], table[0], lengths[0], None,
            query_pos=None, window=None, softcap=None, impl="pallas",
        )
        return part.o

    fn = shmap(
        body, mesh4,
        in_specs=(P(None), P("data"), P("data"), P("data"), P("data")),
        out_specs=P("data"),
    )
    pages = _spec(sh, (n, n_pages, page, KVH, D), jnp.float32)
    _compile(
        fn, _spec(rep, (b, 1, H, D), jnp.bfloat16), pages, pages,
        _spec(sh, (n, b, max_pages), jnp.int32),
        _spec(sh, (n, b), jnp.int32),
    )


def _kernel_fn(kernel, s):
    """(function, argument specs) running one main-path kernel at small
    shapes."""
    from repro.kernels import ops

    t = 256
    if kernel == "paged_decode_attn":
        pages = s((64, 16, KVH, D), jnp.float32)
        return (lambda q, kp, vp, table, lengths: ops.paged_decode_partial(
            q, kp, vp, table, lengths, impl="pallas").o,
            (s((2, 1, H, D), jnp.bfloat16), pages, pages,
             s((2, 8), jnp.int32), s((2,), jnp.int32)))
    q, kv = s((t, H, D), jnp.bfloat16), s((t, KVH, D), jnp.bfloat16)
    off = s((3,), jnp.int32)
    if kernel == "prefill_packed_attn":
        return (lambda q, k, v, o: ops.prefill_packed(q, k, v, o,
                                                      impl="pallas"),
                (q, kv, kv, off))
    state = (s((t, H, D), jnp.float32), s((t, H), jnp.float32),
             s((t, H), jnp.float32))
    return (lambda q, k, v, qo, ko, o, m, l: ops.prefill_ring_chunk(
        q, k, v, qo, ko, (o, m, l), q_shard=1, k_shard=0, n_shards=2,
        impl="pallas"), (q, kv, kv, off, off) + state)


@pytest.mark.parametrize("kernel", ["paged_decode_attn", "prefill_packed_attn",
                                    "prefill_ring_chunk_attn"])
def test_kernel_carries_its_name(one_chip, no_persistent_cache, kernel):
    """Each main-path kernel's custom call carries its name in the
    ``kernel_metadata`` attribute, which the profiler prints into the
    device op's name."""
    fn, args = _kernel_fn(kernel, lambda shape, dt: _spec(one_chip, shape, dt))
    text = _compile(fn, *args).as_text()
    i = text.index("kernel_metadata=")
    assert f'"kernel":"{kernel}"' in text[i:i + 80], text[i:i + 80]
