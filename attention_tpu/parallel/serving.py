"""Sharded autoregressive decoding: serve a KV cache across a mesh.

The serving-side counterpart of the training-time parallel strategies —
not in the reference (whose kernel is one-shot batch, `attention-mpi.c`),
but required for the framework's decode path (`ops/decode.py`) to scale
the way the batch path does:

  * :func:`head_sharded_decode` — tensor-parallel serving: the KV cache
    (and the q-head groups that read it) sharded over KV heads.  Fully
    embarrassingly parallel: zero collectives per token; each chip
    streams only its own cache shard.
  * :func:`cache_sharded_decode` — sequence-parallel serving for caches
    too large for one chip's HBM: cache *rows* sharded over the mesh,
    per-shard online-softmax partials merged with the same two-phase
    pmax/psum scheme as the batch path (`kv_sharded.merge_partials`,
    the reference's `attention-mpi.c:340-380` algorithm applied to a
    single query row).
  * :func:`head_sharded_decode_quantized` / :func:`head_sharded_decode_paged`
    — the tensor-parallel layout applied to the int8 and paged cache
    types (values+scales / pools shard by KV head; page tables
    replicate), so every cache type the framework serves also serves
    sharded.
  * :func:`head_sharded_prefill` — the batch flash kernel (cached
    prefill / chunked append) under the same head sharding, so a
    ``tp_axis`` model's whole generate loop stays sharded.
  * :func:`head_sharded_ragged_step` — the serving engine's packed
    single-launch step (`ops.ragged_paged` append + attention) under
    the same head sharding: pools and new K/V rows shard by KV head,
    every host-packed index array replicates, both halves run inside
    one shard_map — ``EngineConfig.mesh_shards`` lowers onto this.

Both are `shard_map`s over a 1D mesh axis and compose with an outer
batch/data-parallel axis via pjit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from attention_tpu.ops.decode import flash_decode
from attention_tpu.ops.flash import BlockSizes, flash_attention_partials
from attention_tpu.parallel.kv_sharded import merge_partials
from attention_tpu.parallel.mesh import default_mesh


class MeshConfigError(ValueError):
    """A sharded serving call's geometry cannot split over the mesh.

    Raised at CALL time when the KV-head count does not divide by the
    mesh-axis size (an uneven split would silently mis-slice the
    contiguous head chunk GQA groups depend on), or by the serving
    engine when ``EngineConfig.mesh_shards`` asks for more devices
    than the runtime exposes.  Subclasses ValueError so existing
    argument-validation callers keep working; typed so mesh-serving
    callers can distinguish "fix your shard count" from a kernel
    bug."""


def _head_sharded_call(q, hkv, mesh, axis_name, kernel, operands,
                       operand_specs):
    """Shared tensor-parallel scaffold for every cache type: validate
    KV-head divisibility, shard ``q`` (and whatever cache pytree
    ``operands`` carries, per ``operand_specs``) along the KV-head dim,
    and run ``kernel`` per shard.  Adding a decode option means
    threading it through ONE wrapper's kernel closure, not three copies
    of this plumbing."""
    if mesh is None:
        mesh = default_mesh(axis_name)
    n_dev = mesh.shape[axis_name]
    if hkv % n_dev:
        raise MeshConfigError(
            f"kv heads {hkv} not divisible by mesh size {n_dev}"
        )
    # q is (B, H, d) for decode, (B, H, S, d) for prefill — heads at dim 1
    q_spec = P(None, axis_name, *([None] * (q.ndim - 2)))

    @functools.partial(
        shard_map,
        mesh=mesh,
        check_vma=False,
        in_specs=(q_spec, *operand_specs),
        out_specs=q_spec,
    )
    def run(q_local, *ops):
        return kernel(q_local, *ops)

    return run(q, *operands)


def head_sharded_prefill(q, k, v, *, mesh=None, axis_name="tp", **kw):
    """Batch flash attention (cached prefill / chunked append) with the
    heads sharded over ``axis_name`` — per-head math is independent, so
    the shard_map needs no collectives and contiguous head chunks keep
    GQA groups aligned.  ``kw`` passes straight to
    :func:`ops.flash.flash_attention`; traced scalars in it (q_offset,
    kv_valid) ride in as replicated closures.  Shapes: (B, H, S, d)."""
    from attention_tpu.ops.flash import flash_attention

    spec = P(None, axis_name, None, None)

    def kernel(q_local, k_local, v_local):
        return flash_attention(q_local, k_local, v_local, **kw)

    return _head_sharded_call(
        q, k.shape[1], mesh, axis_name, kernel, (k, v), (spec, spec),
    )


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "axis_name", "scale", "block_k", "interpret",
                     "softcap", "window", "sinks"),
)
def head_sharded_decode(
    q: jax.Array,        # (B, H, d)
    k_cache: jax.Array,  # (B, Hkv, N, d)
    v_cache: jax.Array,  # (B, Hkv, N, dv)
    lengths: jax.Array,  # (B,) or scalar
    *,
    mesh: Mesh | None = None,
    axis_name: str = "tp",
    scale: float | None = None,
    block_k: int = 2048,
    interpret: bool | None = None,
    softcap: float | None = None,
    window: int | None = None,
    sinks: int | None = None,
) -> jax.Array:
    """Tensor-parallel decode: KV heads sharded, zero collectives.

    Contiguous head chunks keep q-head -> kv-head groups aligned per
    device (q head j reads kv head j // group; chunk r holds q heads
    [r·H/R, (r+1)·H/R) and exactly their kv heads [r·Hkv/R, ...)), so
    each chip runs a complete :func:`flash_decode` on its slice.

    A 4-D ``q`` (B, H, S, d) runs the speculative-verify chunk kernel
    (:func:`ops.decode.flash_decode_chunk`) per head shard instead —
    ``lengths`` is then the post-append length.
    """
    lens = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (q.shape[0],))
    c_spec = P(None, axis_name, None, None)

    def kernel(q_local, k_local, v_local, lens_full):
        if q_local.ndim == 4:
            from attention_tpu.ops.decode import flash_decode_chunk

            return flash_decode_chunk(
                q_local, k_local, v_local, lens_full,
                scale=scale, block_k=block_k, interpret=interpret,
                softcap=softcap, window=window, sinks=sinks,
            )
        return flash_decode(
            q_local, k_local, v_local, lens_full,
            scale=scale, block_k=block_k, interpret=interpret,
            softcap=softcap, window=window, sinks=sinks,
        )

    return _head_sharded_call(
        q, k_cache.shape[1], mesh, axis_name, kernel,
        (k_cache, v_cache, lens), (c_spec, c_spec, P(None)),
    )


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "axis_name", "scale", "block_k", "interpret",
                     "softcap", "window", "sinks"),
)
def head_sharded_decode_quantized(
    q: jax.Array,  # (B, H, d)
    cache,         # ops.quant.QuantizedKV (int8 values + fp32 scales)
    lengths: jax.Array,  # (B,) or scalar
    *,
    mesh: Mesh | None = None,
    axis_name: str = "tp",
    scale: float | None = None,
    block_k: int = 4096,
    interpret: bool | None = None,
    softcap: float | None = None,
    window: int | None = None,
    sinks: int | None = None,
) -> jax.Array:
    """Tensor-parallel decode against an int8 KV cache.

    The same contiguous-head-chunk layout as :func:`head_sharded_decode`
    applied to every field of the ``QuantizedKV`` pytree (values AND
    their sublane-replicated scales shard along the KV-head dim), so
    each chip runs a complete :func:`flash_decode_quantized` on its
    slice — zero collectives per token, at 0.63x the per-chip cache HBM
    of the bf16 path.  ``window``/``sinks`` serve sliding-window models
    through the same sharding.
    """
    from attention_tpu.ops.quant import QuantizedKV, flash_decode_quantized

    lens = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (q.shape[0],))
    f_spec = P(None, axis_name, None, None)  # every field: (B, Hkv, ...)
    cache_specs = QuantizedKV(f_spec, f_spec, f_spec, f_spec)

    def kernel(q_local, cache_local, lens_full):
        if q_local.ndim == 4:  # speculative-verify chunk (see
            # head_sharded_decode): per-shard chunk kernel, same layout
            from attention_tpu.ops.quant import (
                flash_decode_quantized_chunk,
            )

            return flash_decode_quantized_chunk(
                q_local, cache_local, lens_full,
                scale=scale, block_k=block_k, interpret=interpret,
                softcap=softcap, window=window, sinks=sinks,
            )
        return flash_decode_quantized(
            q_local, cache_local, lens_full,
            scale=scale, block_k=block_k, interpret=interpret,
            softcap=softcap, window=window, sinks=sinks,
        )

    return _head_sharded_call(
        q, cache.k_q.shape[1], mesh, axis_name, kernel,
        (cache, lens), (cache_specs, P(None)),
    )


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "axis_name", "scale", "interpret", "softcap",
                     "window", "sinks"),
)
def head_sharded_decode_paged(
    q: jax.Array,  # (B, H, d)
    cache,         # ops.paged.PagedKV (pools + page table + lengths)
    *,
    mesh: Mesh | None = None,
    axis_name: str = "tp",
    scale: float | None = None,
    interpret: bool | None = None,
    softcap: float | None = None,
    window: int | None = None,
    sinks: int | None = None,
) -> jax.Array:
    """Tensor-parallel decode through a paged KV pool.

    The physical pools (P, Hkv, page_size, d) shard along their KV-head
    dim; the page table and lengths replicate (page ids are head-
    agnostic), so each chip translates the same logical pages into its
    own head slice of the pool and runs a complete
    :func:`paged_flash_decode` — zero collectives per token.  A serving
    stack can therefore combine prefix sharing (forked page tables) with
    tensor parallelism without resharding the pool.
    """
    from attention_tpu.ops.paged import PagedKV, paged_flash_decode

    pool_spec = P(None, axis_name, None, None)
    cache_specs = PagedKV(pool_spec, pool_spec, P(None, None), P(None))

    def kernel(q_local, cache_local):
        return paged_flash_decode(
            q_local, cache_local,
            scale=scale, interpret=interpret,
            softcap=softcap, window=window, sinks=sinks,
        )

    return _head_sharded_call(
        q, cache.k_pool.shape[1], mesh, axis_name, kernel,
        (cache,), (cache_specs,),
    )


def head_sharded_ragged_step(
    q: jax.Array,      # (1, Hq, T, d) packed token axis
    cache,             # ops.ragged_paged.RaggedPagedStep
    k_new: jax.Array,  # (1, Hkv, T, d) this step's new K rows
    v_new: jax.Array,  # (1, Hkv, T, d)
    *,
    mesh: Mesh | None = None,
    axis_name: str = "tp",
    softcap: float | None = None,
    window: int | None = None,
    sinks: int | None = None,
):
    """The packed serving step (append + ragged attention) with KV
    heads sharded over ``axis_name`` — the engine's single-launch
    lowering made tensor-parallel.

    Both halves of the step run INSIDE one shard_map so the pool
    append and the attention read stay a single per-shard program:
    the physical pools (P, Hkv, page_size, d) and this step's new K/V
    rows shard along their KV-head dim, while every host-packed index
    array — page tables, ``kv_lens``, ``cu_q_lens``, the decode/
    prefill ``distribution``, per-token position/slot, the ``q_span``
    tile marker — replicates (page ids and packing are head-agnostic).
    Contiguous head chunks keep GQA groups aligned per shard (the
    `head_sharded_decode` layout), so each device appends to and
    scores only its own head slice: zero collectives per step.  The
    post-append ``kv_lens`` is recomputed identically on every shard
    from replicated inputs, so the returned cache's replicated
    out-spec is exact, not approximate.

    Returns ``(out, cache)`` exactly like the single-device
    ``ragged_paged_append`` + ``ragged_paged_attention`` pair.
    """
    from attention_tpu.ops.ragged_paged import (
        RaggedPagedStep,
        ragged_paged_append,
        ragged_paged_attention,
    )

    if mesh is None:
        mesh = default_mesh(axis_name)
    n_dev = mesh.shape[axis_name]
    hkv = cache.k_pool.shape[1]
    if hkv % n_dev:
        raise MeshConfigError(
            f"kv heads {hkv} not divisible by mesh size {n_dev}"
        )
    if q.shape[1] % n_dev:
        raise MeshConfigError(
            f"q heads {q.shape[1]} not divisible by mesh size {n_dev}"
        )
    head_spec = P(None, axis_name, None, None)
    rep1 = P(None)
    cache_specs = RaggedPagedStep(
        k_pool=head_spec, v_pool=head_spec,
        page_table=P(None, None), kv_lens=rep1, cu_q_lens=rep1,
        distribution=rep1, token_pos=rep1, token_slot=rep1,
        q_span=rep1,
    )

    @functools.partial(
        shard_map,
        mesh=mesh,
        check_vma=False,
        in_specs=(head_spec, cache_specs, head_spec, head_spec),
        out_specs=(head_spec, cache_specs),
    )
    def run(q_local, cache_local, k_local, v_local):
        cache_local = ragged_paged_append(cache_local, k_local, v_local)
        out = ragged_paged_attention(
            q_local, cache_local, softcap=softcap, window=window,
            sinks=sinks,
        )
        return out, cache_local

    return run(q, cache, k_new, v_new)


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "axis_name", "scale", "block_sizes",
                     "softcap"),
)
def cache_sharded_decode(
    q: jax.Array,        # (B, H, d)
    k_cache: jax.Array,  # (B, Hkv, N, d)
    v_cache: jax.Array,  # (B, Hkv, N, dv)
    length: jax.Array,   # scalar valid length (uniform batch)
    *,
    mesh: Mesh | None = None,
    axis_name: str = "sp",
    scale: float | None = None,
    block_sizes: BlockSizes | None = None,
    softcap: float | None = None,
) -> jax.Array:
    """Sequence-parallel decode: cache *rows* sharded over the mesh.

    Each device computes online-softmax partials over its cache shard
    (kv_valid clipped to the shard's slice of the valid prefix), then
    the two-phase pmax/psum merge normalizes globally — one query row's
    worth of the reference's distributed softmax (SURVEY §3.3).
    """
    if mesh is None:
        mesh = default_mesh(axis_name)
    n_dev = mesh.shape[axis_name]
    b, h, d = q.shape
    _, hkv, n, dv = v_cache.shape
    if n % n_dev:
        raise ValueError(
            f"cache capacity {n} not divisible by mesh size {n_dev}"
        )
    if h % hkv:
        raise ValueError(f"q heads {h} not a multiple of kv heads {hkv}")
    group = h // hkv
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    shard_n = n // n_dev
    length = jnp.asarray(length, jnp.int32).reshape(())

    # Each (batch, kv-head) pair becomes one kernel head whose q rows are
    # the GQA group — the same layout trick as `flash_decode`.
    qs = q.reshape(b * hkv, group, d)
    kc = k_cache.reshape(b * hkv, n, d)
    vc = v_cache.reshape(b * hkv, n, dv)

    c_spec = P(None, axis_name, None)

    @functools.partial(
        shard_map,
        mesh=mesh,
        check_vma=False,
        in_specs=(P(), c_spec, c_spec, P()),
        out_specs=P(),
    )
    def run(q_full, k_local, v_local, length_full):
        idx = lax.axis_index(axis_name)
        kv_valid = jnp.clip(length_full - idx * shard_n, 0, shard_n)
        out_un, lmax, lsum = flash_attention_partials(
            q_full, k_local, v_local, scale=scale,
            block_sizes=block_sizes, kv_valid=kv_valid,
            softcap=softcap,
        )
        return merge_partials(out_un, lmax, lsum, axis_name)

    out = run(qs, kc, vc, length)  # (b*hkv, group, dv), replicated
    return out.reshape(b, h, dv).astype(v_cache.dtype)
