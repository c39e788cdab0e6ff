"""jit'd dispatch wrappers for the Pallas kernels.

impl:
  * "xla"        — pure-jnp reference math (the CPU path and the SPMD
                   dry-run; it also takes traced shard ids directly);
  * "pallas"     — the Pallas TPU kernel (compiled for TPU);
  * "interpret"  — the Pallas kernel body executed in interpret mode (CPU
                   validation of the TPU kernel).

The default impl follows the backend: "pallas" on a TPU, "xla" elsewhere.
It is decided at the first dispatch (not at import, so the backend is the
one the process really runs on).  ``REPRO_KERNEL_IMPL`` overrides it without
code edits (benchmarks / CI), and `set_default_impl` programmatically.

`dispatch_counts` tracks kernel/dispatch call volume per entry point so tests
and benchmarks can assert launch-count invariants (e.g. one paged decode
launch per instance per layer, independent of batch size).
"""
from __future__ import annotations

import os
from collections import Counter
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_decode import flash_decode_partial as _fd_kernel
from repro.kernels.paged_flash_decode import (
    paged_flash_decode_partial as _pfd_kernel,
)
from repro.kernels.paged_flash_prefill import (
    packed_flash_prefill as _pfp_kernel,
    packed_flash_prefill_ring_chunk as _pfp_ring_kernel,
)
from repro.kernels.striped_attention import striped_flash_attention as _sa_kernel
from repro.models.attention import Partial

_VALID_IMPLS = ("xla", "pallas", "interpret")


class TransientDispatchError(RuntimeError):
    """A kernel dispatch failed transiently (injected by the chaos harness
    or raised by a flaky backend).  The engine retries with bounded backoff
    before declaring the instance failed — see engine/server.py."""


# Fault-injection seam: when set, every dispatch entry point (and the
# executors' per-batch dispatch guards) calls the hook with a point name
# BEFORE doing any work; the hook may raise TransientDispatchError to
# simulate a flaky launch.  Raising happens before any compute or KV write,
# so a retried dispatch is side-effect free.  `None` (the default) is
# zero-overhead beyond one attribute read.
_fault_hook = None


def set_fault_hook(hook) -> None:
    """Install (or clear, with None) the dispatch fault hook."""
    global _fault_hook
    _fault_hook = hook


def check_fault(point: str) -> None:
    """Raise-point consulted at the top of every dispatch entry.  NOTE:
    jitted callers only reach the ops wrappers at trace time (cached
    programs never re-enter Python), so the executors additionally call
    this per batch dispatch — those are the reliable injection points."""
    if _fault_hook is not None:
        _fault_hook(point)


def _resolve_default_impl() -> str:
    """``REPRO_KERNEL_IMPL`` if set, else the backend's own: the Pallas
    kernels on a TPU, the XLA reference math anywhere else."""
    impl = os.environ.get("REPRO_KERNEL_IMPL")
    if impl is None:
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl not in _VALID_IMPLS:
        raise ValueError(
            f"REPRO_KERNEL_IMPL={impl!r}: expected one of {_VALID_IMPLS}"
        )
    return impl


_DEFAULT_IMPL: Optional[str] = None  # resolved at the first dispatch

dispatch_counts: Counter = Counter()

# communication accounting (bytes, per entry point): ring ppermute legs are
# counted at trace time from their (static) per-rank payload shapes, so one
# compile of an SPMD program yields the exact per-leg byte volume without
# instrumenting the runtime.
comm_bytes: Counter = Counter()


def reset_dispatch_counts() -> None:
    dispatch_counts.clear()
    comm_bytes.clear()


def set_default_impl(impl: str) -> None:
    global _DEFAULT_IMPL
    assert impl in _VALID_IMPLS
    _DEFAULT_IMPL = impl


def get_default_impl() -> str:
    global _DEFAULT_IMPL
    if _DEFAULT_IMPL is None:
        _DEFAULT_IMPL = _resolve_default_impl()
    return _DEFAULT_IMPL


def attention(
    q, k, v, q_pos, k_pos, *, causal=True, window=None, softcap=None,
    impl: Optional[str] = None, block_q: int = 128, block_k: int = 128,
):
    impl = impl or get_default_impl()
    check_fault("attention")
    dispatch_counts["attention"] += 1
    if impl == "xla":
        return ref.striped_flash_attention_ref(
            q, k, v, q_pos, k_pos, causal=causal, window=window, softcap=softcap
        )
    return _sa_kernel(
        q, k, v, jnp.asarray(q_pos), jnp.asarray(k_pos), causal=causal,
        window=window, softcap=softcap, block_q=block_q, block_k=block_k,
        interpret=(impl == "interpret"),
    )


def decode_partial(
    q, k, v, lengths, *, k_pos_offset=0, window=None, softcap=None,
    impl: Optional[str] = None, block_k: int = 128,
) -> Partial:
    """Per-request decode over a dense KV shard (legacy gather-dense path)."""
    impl = impl or get_default_impl()
    check_fault("decode_partial")
    dispatch_counts["decode_partial"] += 1
    if impl == "xla":
        return ref.flash_decode_partial_ref(
            q, k, v, lengths, k_pos_offset=k_pos_offset, window=window,
            softcap=softcap,
        )
    return _fd_kernel(
        q, k, v, lengths, k_pos_offset=k_pos_offset, window=window,
        softcap=softcap, block_k=block_k, interpret=(impl == "interpret"),
    )


def prefill_packed(
    q, k, v, seq_offsets, *, window=None, softcap=None, max_seq_len=None,
    impl: Optional[str] = None, block_q: Optional[int] = None,
    block_k: Optional[int] = None,
):
    """Packed ragged causal prefill: ONE launch for a whole prefill batch
    concatenated on a single token axis (see kernels/paged_flash_prefill.py).
    ``max_seq_len`` (static) bounds the banded XLA fallback's reach; the
    Pallas kernel skips non-interacting tiles from the prefetched offsets.
    ``block_q``/``block_k`` ``None``: the kernel chooses its tiles from the
    shapes (`choose_tiles`); the banded fallback's q block is ``block_q``
    or `ref.BAND`."""
    impl = impl or get_default_impl()
    check_fault("prefill_packed")
    dispatch_counts["prefill_packed"] += 1
    if impl == "xla":
        return ref.packed_prefill_banded(
            q, k, v, seq_offsets, window=window, softcap=softcap,
            block_q=block_q or ref.BAND, max_seq_len=max_seq_len,
        )
    return _pfp_kernel(
        q, k, v, jnp.asarray(seq_offsets, jnp.int32), window=window,
        softcap=softcap, block_q=block_q, block_k=block_k,
        interpret=(impl == "interpret"),
    )


def prefill_ring_chunk(
    q, k, v, q_offsets, k_offsets, carry=None, *,
    q_shard: int, k_shard: int, n_shards: int,
    window=None, softcap=None, max_seq_len=None,
    impl: Optional[str] = None, block_q: Optional[int] = None,
    block_k: Optional[int] = None,
):
    """One ring step of the DoP>1 ESP packed prefill: fold one striped KV
    chunk into the carried unnormalized (o, m, l) flash state with a single
    ragged launch (see kernels/paged_flash_prefill.py — ring fusion).

    ``q_offsets``/``k_offsets`` are the per-shard recomputed segment offsets
    (`striped.shard_offsets`) the kernel/banded fallback derive segment ids
    from; causal/window masks evaluate on global striped positions.
    ``carry=None`` starts an empty state (m=-inf).  Finalize after the last
    step with ``o / l`` (l==0 rows are bucket padding).  Tiles as in
    `prefill_packed`."""
    impl = impl or get_default_impl()
    check_fault("prefill_ring_chunk")
    dispatch_counts["prefill_ring_chunk"] += 1
    if carry is None:
        tl, h = q.shape[0], q.shape[1]
        carry = (
            jnp.zeros((tl, h, q.shape[2]), jnp.float32),
            jnp.full((tl, h), -jnp.inf, jnp.float32),
            jnp.zeros((tl, h), jnp.float32),
        )
    if impl == "xla":
        return ref.packed_prefill_ring_chunk_banded(
            q, k, v, q_offsets, k_offsets, carry,
            q_shard=q_shard, k_shard=k_shard, n_shards=n_shards,
            window=window, softcap=softcap, block_q=block_q or ref.BAND,
            max_seq_len=max_seq_len,
        )
    return _pfp_ring_kernel(
        q, k, v, jnp.asarray(q_offsets, jnp.int32),
        jnp.asarray(k_offsets, jnp.int32), carry,
        q_shard=q_shard, k_shard=k_shard, n_shards=n_shards,
        window=window, softcap=softcap, block_q=block_q, block_k=block_k,
        interpret=(impl == "interpret"),
    )


def _payload_bytes(operands) -> int:
    """Per-rank payload bytes of a collective's operands (static shapes
    inside a shard_map body make trace-time accounting exact)."""
    return sum(
        int(x.size) * jnp.dtype(x.dtype).itemsize
        for x in jax.tree_util.tree_leaves(operands)
    )


def ring_ppermute(operands, axis_name: str, pairs):
    """`lax.ppermute` wrapper for the SPMD prefill ring: forwards the KV
    chunk (and its per-shard offsets / any carried metadata) to the ring
    neighbour, counting one dispatch and the exact per-rank payload bytes
    (shapes are static inside the shard_map body, so trace-time accounting
    is exact).  Every ring leg of the mesh executor goes through here so
    tests and benchmarks can assert/record the communication volume."""
    dispatch_counts["ring_ppermute"] += 1
    comm_bytes["ring_ppermute"] += _payload_bytes(operands)
    return jax.lax.ppermute(operands, axis_name, pairs)


def psum(operands, axis_name: str):
    """Counted `lax.psum`: the SPMD decode LSE-merge reduces the weighted
    (o·exp(m-M), l·exp(m-M)) accumulators across the KV shards through here,
    so `comm_bytes` covers decode traffic the same way `ring_ppermute`
    covers the prefill ring.  Bytes are per-rank payload (the reduced tensor
    size), not wire volume — the all-reduce algorithm is the backend's."""
    dispatch_counts["psum"] += 1
    comm_bytes["psum"] += _payload_bytes(operands)
    return jax.lax.psum(operands, axis_name)


def pmax(operands, axis_name: str):
    """Counted `lax.pmax` (the decode merge's global running-max M)."""
    dispatch_counts["pmax"] += 1
    comm_bytes["pmax"] += _payload_bytes(operands)
    return jax.lax.pmax(operands, axis_name)


def psum_scatter(operands, axis_name: str, *, scatter_dimension: int = 0,
                 tiled: bool = True):
    """Counted `lax.psum_scatter`: the batch-sharded decode merge reduces
    the weighted (o·exp(m-M), l·exp(m-M)) accumulators AND hands each rank
    only its own batch slice of the result in one collective — the paper's
    "send back partial results" addressed to the masters (§4.2) instead of
    replicated everywhere.  Bytes are the per-rank payload CONTRIBUTED
    (the full pre-scatter tensor), like `psum`."""
    dispatch_counts["psum_scatter"] += 1
    comm_bytes["psum_scatter"] += _payload_bytes(operands)
    return jax.tree.map(
        lambda x: jax.lax.psum_scatter(
            x, axis_name, scatter_dimension=scatter_dimension, tiled=tiled
        ),
        operands,
    )


def all_gather(operands, axis_name: str, *, axis: int = 0, tiled: bool = True):
    """Counted `lax.all_gather`: the batch-sharded decode boundary's q-slice
    exchange (every rank needs the full-batch query against its local KV)
    and the in-program sampled-token / new-KV exchanges go through here so
    `comm_bytes` covers them.  Bytes are the per-rank payload contributed
    (the LOCAL slice each rank injects)."""
    dispatch_counts["all_gather"] += 1
    comm_bytes["all_gather"] += _payload_bytes(operands)
    return jax.tree.map(
        lambda x: jax.lax.all_gather(x, axis_name, axis=axis, tiled=tiled),
        operands,
    )


def count_transfer(key: str, operands) -> None:
    """Account an explicit host-driven device transfer (e.g. the per-shard
    decode loop's q broadcast / partial pull-home in `core.paged_decode`)
    under `comm_bytes[key]` — decode comm stays visible to benchmarks even
    on the non-SPMD path."""
    comm_bytes[key] += _payload_bytes(operands)


def paged_decode_partial(
    q, k_pages, v_pages, block_table, lengths, page_pos=None, *,
    query_pos=None, window=None, softcap=None, impl: Optional[str] = None,
) -> Partial:
    """Batched ragged decode over the paged pool: ONE launch for every
    request of this instance (see kernels/paged_flash_decode.py)."""
    impl = impl or get_default_impl()
    check_fault("paged_decode_partial")
    dispatch_counts["paged_decode_partial"] += 1
    if impl == "xla":
        return ref.paged_flash_decode_partial_ref(
            q, k_pages, v_pages, block_table, lengths, page_pos,
            query_pos=query_pos, window=window, softcap=softcap,
        )
    return _pfd_kernel(
        q, k_pages, v_pages, jnp.asarray(block_table),
        jnp.asarray(lengths), page_pos,
        query_pos=query_pos, window=window, softcap=softcap,
        interpret=(impl == "interpret"),
    )
