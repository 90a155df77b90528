"""Flax attention modules built on the framework's kernels.

The reference is a bare kernel with no model around it; these modules are
the "model family" surface a framework user needs: a grouped-query
self-attention layer (BASELINE config 5: 32 Q heads / 4 KV heads) whose
inner op is selectable between the differentiable fused flash path and
the auto-SPMD XLA path.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from attention_tpu.ops.decode import flash_decode
from attention_tpu.ops.paged import PagedKV, paged_append, paged_flash_decode
from attention_tpu.ops.ragged_paged import (
    RaggedPagedStep,
    ragged_paged_append,
    ragged_paged_attention,
)
from attention_tpu.ops.flash import flash_attention
from attention_tpu.ops.flash_vjp import flash_attention_diff
from attention_tpu.ops.quant import (
    QuantizedKV,
    flash_decode_quantized,
    quantize_kv,
    sink_read_rotation,
    update_quantized_kv,
)
from attention_tpu.ops.reference import attention_xla
from attention_tpu.ops.rope import apply_rope


class KVCache(NamedTuple):
    """Per-layer decode cache: K/V (B, Hkv, N, dh) + valid length.

    ``length`` is a traced int32 scalar (uniform across the batch —
    prefill is batched on equal-length prompts; `flash_decode` itself
    also accepts per-sequence (B,) lengths for ragged serving).
    """

    k: jax.Array
    v: jax.Array
    length: jax.Array

    @classmethod
    def create(cls, batch: int, num_kv_heads: int, capacity: int,
               head_dim: int, dtype=jnp.bfloat16) -> "KVCache":
        shape = (batch, num_kv_heads, capacity, head_dim)
        return cls(
            k=jnp.zeros(shape, dtype),
            v=jnp.zeros(shape, dtype),
            length=jnp.zeros((), jnp.int32),
        )

    def quantize(self) -> "QuantKVCache":
        """One-shot int8 conversion (after prefill): 0.63x the HBM for
        the rest of the decode loop; the bf16 arrays can then be freed."""
        return QuantKVCache(kv=quantize_kv(self.k, self.v),
                            length=self.length)


class QuantKVCache(NamedTuple):
    """int8 decode cache: `QuantizedKV` (int8 values + scales) + valid length.

    Decode-only (S == 1 steps, ``impl='flash'``): the serving flow is
    bf16 prefill -> :meth:`KVCache.quantize` -> int8 decode loop.
    """

    kv: QuantizedKV
    length: jax.Array


class RollingKVCache(NamedTuple):
    """Ring-buffer cache for sliding-window models (optionally with
    StreamingLLM attention sinks): memory is bounded by sinks + window,
    NOT the sequence length, however long generation runs.

    Slot layout: pinned sink slots ``[0, sinks)`` hold the first
    ``sinks`` tokens forever; ring slots ``[sinks, sinks + window)``
    hold the last ``window`` tokens in wrapped order (token t sits at
    ``sinks + (t - sinks) % window`` once past the sinks).  Capacity
    rounds ``sinks + window`` up to the decode kernel's 128-row
    granule; tail slots are never written and reads mask by the valid
    count.  Correctness rests on softmax being permutation-invariant
    over KV rows.  ``length`` counts total tokens seen.
    """

    k: jax.Array  # (B, Hkv, C, dh)
    v: jax.Array
    length: jax.Array

    @classmethod
    def create(cls, batch: int, num_kv_heads: int, window: int,
               head_dim: int, dtype=jnp.bfloat16,
               sinks: int = 0) -> "RollingKVCache":
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        cap = cls.capacity_for(window, sinks)
        shape = (batch, num_kv_heads, cap, head_dim)
        return cls(
            k=jnp.zeros(shape, dtype),
            v=jnp.zeros(shape, dtype),
            length=jnp.zeros((), jnp.int32),
        )

    @property
    def capacity(self) -> int:
        return self.k.shape[2]

    @staticmethod
    def capacity_for(window: int, sinks: int = 0) -> int:
        """sinks pinned slots + window ring slots, rounded up to the
        decode kernel's 128-row granule (tail slots stay unused — reads
        mask by the valid count, which never exceeds sinks + window)."""
        return -(-(window + sinks) // 128) * 128


class RaggedKVCache(NamedTuple):
    """Decode cache with PER-SEQUENCE valid lengths (B,) — the ragged
    serving cache: one batch mixes prompts of different lengths with no
    host-side bucketing.

    Built from a padded-prompt prefill on the scalar `KVCache` (causal
    masking keeps pad keys invisible to valid queries), then decode
    steps write each sequence's new row at its own ``lengths[b]`` and
    attend over its own valid prefix (`flash_decode` takes (B,) lens
    natively).  Pad rows are progressively overwritten by decode.
    """

    k: jax.Array  # (B, Hkv, N, dh)
    v: jax.Array
    lengths: jax.Array  # (B,) int32 valid rows per sequence

    @property
    def length(self):
        """Per-sequence lengths (named like the other caches so shared
        code — RoPE offsets — treats caches uniformly)."""
        return self.lengths

    @classmethod
    def from_prefill(cls, cache: KVCache, lengths) -> "RaggedKVCache":
        return cls(cache.k, cache.v, jnp.asarray(lengths, jnp.int32))


def _xla_mha(q, k, v, *, causal, window=None, softcap=None, sinks=0):
    """Dense attention on (B, H, S, dh) with GQA head repeat; differentiable
    and auto-partitionable by XLA under pjit shardings."""
    if not causal:
        hq, hkv = q.shape[1], k.shape[1]
        if hq != hkv:
            k = jnp.repeat(k, hq // hkv, axis=1)
            v = jnp.repeat(v, hq // hkv, axis=1)
        return attention_xla(q, k, v, softcap=softcap)
    # causal = the start=0, fully-valid instance of the cached mask
    return _xla_cached_attention(q, k, v, start=0, new_len=k.shape[2],
                                 causal=True, window=window,
                                 softcap=softcap, sinks=sinks)


def _flash_mha(q, k, v, *, causal, window=None, softcap=None, sinks=0):
    # max_mode="bound": the library's fastest exact kernel (same output
    # and lse as the online recurrence — tests/test_ops.py pins it;
    # 0.92-0.97 vs 0.78-0.82 MXU util, scripts/max_mode_exp.py)
    return flash_attention_diff(q, k, v, causal=causal, window=window,
                                softcap=softcap, sinks=sinks or None,
                                max_mode="bound")


def _sink_read_keys(kc, new_total, window, sinks, theta):
    """StreamingLLM positional convention for RoPE'd sink keys, applied
    at read time.

    Keys are cached already-rotated at their absolute positions, which
    is exact for window keys (query-to-key distance stays < window) but
    lets the query-to-SINK distance grow without bound once the stream
    passes ``sinks + window`` — outside the rotation range the model was
    trained on.  The paper assigns positions *within the cache* instead.
    Equivalent formulation used here: shift only the ``sinks`` pinned
    keys forward by ``delta = max(new_total - (window + sinks), 0)``
    (RoPE rotations compose additively), which pins every sink at a
    constant relative distance just before the window start, while the
    query and window keys keep their absolute rotations.  Cost per step:
    a rope over ``sinks`` rows; the stored cache stays absolute.
    """
    delta = jnp.maximum(jnp.asarray(new_total, jnp.int32) - (window + sinks),
                        0)
    if delta.ndim:  # ragged: per-sequence (B,) totals -> (B, 1, 1) pos
        delta = delta[:, None, None]
    rot = apply_rope(kc[:, :, :sinks], delta, theta).astype(kc.dtype)
    # in-place-aliasable write of just the sink rows (a concatenate
    # would copy the whole capacity-sized cache every decode step)
    zero = jnp.zeros((), jnp.int32)
    return jax.lax.dynamic_update_slice(kc, rot, (zero, zero, zero, zero))


def _xla_cached_attention(q, kc, vc, *, start, new_len, causal,
                          window=None, softcap=None, sinks=0):
    """Dense cached attention over (B, H, S, dh) vs full-capacity caches
    (B, Hkv, N, dh), masked to the valid prefix.  Pure einsums — XLA
    auto-partitions it under pjit shardings, the serving analog of
    `_xla_mha`."""
    hq, hkv = q.shape[1], kc.shape[1]
    if hq != hkv:
        kc = jnp.repeat(kc, hq // hkv, axis=1)
        vc = jnp.repeat(vc, hq // hkv, axis=1)
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("bhmd,bhnd->bhmn", q, kc,
                   preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    col = jnp.arange(kc.shape[2])[None, :]
    mask = col < new_len
    if causal:
        row = jnp.arange(q.shape[2])[:, None]
        mask = jnp.logical_and(mask, col <= row + start)
        if window is not None:
            win = col >= row + start - (window - 1)
            if sinks:
                win = jnp.logical_or(win, col < sinks)
            mask = jnp.logical_and(mask, win)
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(vc.dtype)
    return jnp.einsum("bhmn,bhnd->bhmd", p, vc)


ATTN_IMPLS: dict[str, Callable] = {"xla": _xla_mha, "flash": _flash_mha}


class GQASelfAttention(nn.Module):
    """Grouped-query self-attention: (B, S, D) -> (B, S, D).

    ``impl='flash'`` uses the fused Pallas kernel (custom VJP);
    ``impl='xla'`` uses dense einsums that XLA partitions automatically
    under dp/sp/tp shardings (the training default on a mesh).
    """

    num_q_heads: int
    num_kv_heads: int
    head_dim: int
    impl: str = "flash"
    causal: bool = True
    dtype: jnp.dtype = jnp.bfloat16
    window: int | None = None  # sliding-window attention (requires causal)
    attn_sinks: int = 0  # StreamingLLM sinks: first k positions stay attendable
    rope: bool = False  # rotary position embeddings on Q/K
    rope_theta: float = 10000.0
    softcap: float | None = None  # logit soft-capping (Gemma-2 style)
    # RMSNorm over the WHOLE q and k projections (all heads together),
    # ahead of the rotation: the OLMo 2 convention
    qk_norm: bool = False
    # RMSNorm over each HEAD of q and k (one scale of ``head_dim`` for
    # every head, at ``norm_eps``), ahead of the rotation
    head_norm: bool = False
    # a sigmoid OUTPUT GATE: a fourth projection of the input, as wide
    # as q, whose sigmoid multiplies the attention's result ahead of
    # ``o_proj``
    gate: bool = False
    norm_eps: float = 1e-6
    # Context parallelism: when set (training under a mesh whose
    # ``cp_axis`` shards the sequence), batch attention runs a
    # differentiable CP composition — the Pallas flash custom VJP under
    # shard_map — instead of a single-device kernel call.  Requires
    # ``impl='flash'``; ``mesh`` must be the training mesh.
    # ``cp_impl``: "allgather" (`parallel.cp`, KV gathered per device —
    # the default training layout), "ring" (`parallel.ring.
    # ring_attention_diff`, O(n/R) KV memory in both passes — the
    # long-context composition), "zigzag" (the ring with llama-3
    # chunk interleaving: equal per-device work at every step of BOTH
    # passes for causal models), or "ulysses" (`parallel.ulysses`,
    # head/seq all-to-all — two collectives per pass, zero softmax
    # collectives; needs q heads and seq divisible by the cp mesh
    # size).  Decode/cached paths are unaffected.
    cp_axis: str | None = None
    cp_impl: str = "allgather"
    # ``tp_axis``: tensor-parallel SERVING — every cached-path kernel
    # call (decode on dense/rolling/ragged/int8/paged caches, chunked
    # prefill) runs head-sharded over this mesh axis via the
    # `parallel.serving` wrappers, while the projections around it stay
    # in ordinary jit for XLA's auto-SPMD to partition (the same
    # composition as cp_axis uses for training: auto-SPMD everywhere,
    # explicit shard_map only at the Pallas kernel).  Requires
    # ``impl='flash'`` and ``mesh``; the axis size must divide the KV
    # head count.
    tp_axis: str | None = None
    mesh: "jax.sharding.Mesh | None" = None

    @nn.compact
    def __call__(self, x: jax.Array,
                 cache: "KVCache | QuantKVCache | None" = None):
        if self.num_q_heads % self.num_kv_heads != 0:
            raise ValueError(
                f"q heads {self.num_q_heads} not a multiple of kv heads "
                f"{self.num_kv_heads}"
            )
        if self.cp_axis is not None:
            if self.impl != "flash":
                raise ValueError(
                    "cp_axis (context-parallel attention) runs the fused "
                    f"flash path; impl {self.impl!r} is not supported"
                )
            if self.mesh is None:
                raise ValueError("cp_axis requires mesh=")
        if self.tp_axis is not None:
            if self.impl != "flash":
                raise ValueError(
                    "tp_axis (head-sharded serving) runs the fused flash "
                    f"kernels; impl {self.impl!r} is not supported (the "
                    "'xla' impl already auto-partitions under jit)"
                )
            if self.mesh is None:
                raise ValueError("tp_axis requires mesh=")
            if self.tp_axis not in self.mesh.shape:
                raise ValueError(
                    f"tp_axis {self.tp_axis!r} is not an axis of the "
                    f"mesh {tuple(self.mesh.axis_names)}"
                )
            tp_size = self.mesh.shape[self.tp_axis]
            if self.num_kv_heads % tp_size:
                raise ValueError(
                    f"kv heads {self.num_kv_heads} not divisible by "
                    f"tp_axis {self.tp_axis!r} size {tp_size}"
                )
        dense = lambda name, heads: nn.DenseGeneral(  # noqa: E731
            features=(heads, self.head_dim),
            use_bias=False,
            dtype=self.dtype,
            name=name,
        )
        q = dense("q_proj", self.num_q_heads)(x)  # (B, S, Hq, dh)
        k = dense("k_proj", self.num_kv_heads)(x)
        v = dense("v_proj", self.num_kv_heads)(x)
        if self.qk_norm and self.head_norm:
            raise ValueError("qk_norm (the whole projection) and head_norm "
                             "(each head) are two norms of q and k; a "
                             "layer has one")
        if self.head_norm:
            q, k = (nn.RMSNorm(epsilon=self.norm_eps, dtype=self.dtype,
                               name=name)(t)
                    for t, name in ((q, "q_norm"), (k, "k_norm")))
        if self.qk_norm:
            def whole(t, name):
                flat = t.reshape(t.shape[:2] + (-1,))
                return nn.RMSNorm(dtype=self.dtype, name=name)(
                    flat).reshape(t.shape)

            q, k = whole(q, "q_norm"), whole(k, "k_norm")
        q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))  # (B, H, S, dh)
        if self.rope:
            # rotate BEFORE caching: keys are stored already-rotated at
            # their absolute positions (scores depend only on relative
            # position, so cached history never needs re-rotation)
            if isinstance(cache, RaggedPagedStep):
                # packed step: every token carries its own absolute
                # position (mixed decode/prefill share one axis)
                pos = cache.token_pos[None, None, :]
            else:
                off = jnp.asarray(
                    0 if cache is None else cache.length, jnp.int32
                )
                base = jnp.arange(x.shape[1], dtype=jnp.int32)
                if off.ndim:  # ragged: (B,) offsets -> (B, 1, S) positions
                    pos = (off[:, None] + base[None, :])[:, None, :]
                else:
                    pos = off + base
            q = apply_rope(q, pos, self.rope_theta)
            k = apply_rope(k, pos, self.rope_theta)
        if self.window is not None:
            if not self.causal:
                raise ValueError("window requires causal=True")
            if self.window < 1:
                raise ValueError(f"window must be >= 1, got {self.window}")
        if self.attn_sinks and self.window is None:
            raise ValueError("attn_sinks require a windowed model")
        if self.attn_sinks < 0:
            raise ValueError(
                f"attn_sinks must be >= 0, got {self.attn_sinks}"
            )
        if cache is None:
            if self.cp_axis is not None:
                if self.cp_impl in ("ring", "zigzag"):
                    from attention_tpu.parallel.ring import (
                        ring_attention_diff,
                    )

                    out = ring_attention_diff(
                        q, k, v, mesh=self.mesh, axis_name=self.cp_axis,
                        causal=self.causal, window=self.window,
                        sinks=self.attn_sinks or None,
                        softcap=self.softcap,
                        schedule=("zigzag" if self.cp_impl == "zigzag"
                                  else "contiguous"),
                    )
                elif self.cp_impl == "allgather":
                    from attention_tpu.parallel.cp import cp_flash_attention

                    out = cp_flash_attention(
                        q, k, v, mesh=self.mesh, axis_name=self.cp_axis,
                        causal=self.causal, window=self.window,
                        sinks=self.attn_sinks or None,
                        softcap=self.softcap,
                    )
                elif self.cp_impl == "ulysses":
                    from attention_tpu.parallel.ulysses import (
                        ulysses_attention,
                    )

                    out = ulysses_attention(
                        q, k, v, mesh=self.mesh, axis_name=self.cp_axis,
                        causal=self.causal, window=self.window,
                        sinks=self.attn_sinks or None,
                        softcap=self.softcap,
                    )
                else:
                    raise ValueError(
                        f"unknown cp_impl {self.cp_impl!r} (supported: "
                        "['allgather', 'ring', 'zigzag', 'ulysses'])"
                    )
            else:
                out = ATTN_IMPLS[self.impl](q, k, v, causal=self.causal,
                                            window=self.window,
                                            softcap=self.softcap,
                                            sinks=self.attn_sinks)
        elif isinstance(cache, QuantKVCache):
            out, cache = self._quantized_decode(q, k, v, cache)
        elif isinstance(cache, RaggedKVCache):
            out, cache = self._ragged_attention(q, k, v, cache)
        elif isinstance(cache, RaggedPagedStep):
            out, cache = self._ragged_paged_step(q, k, v, cache)
        elif isinstance(cache, PagedKV):
            out, cache = self._paged_attention(q, k, v, cache)
        elif isinstance(cache, RollingKVCache):
            out, cache = self._rolling_attention(q, k, v, cache)
        else:
            out, cache = self._cached_attention(q, k, v, cache)
        out = out.transpose(0, 2, 1, 3).reshape(x.shape[0], x.shape[1], -1)
        if self.gate:
            g = dense("gate_proj", self.num_q_heads)(x)
            out = out * jax.nn.sigmoid(
                g.reshape(out.shape).astype(jnp.float32))
        proj = nn.DenseGeneral(
            features=x.shape[-1], use_bias=False, dtype=self.dtype, name="o_proj"
        )(out.astype(self.dtype))
        return proj if cache is None else (proj, cache)

    def _decode_call(self, q1, kr, vc, lens, **kw):
        """The fused decode kernel — head-sharded over ``tp_axis`` when
        serving tensor-parallel, local otherwise.  Shared by the dense,
        rolling, and ragged cache paths.  A 4-D ``q1`` (B, H, S, d)
        runs the speculative-verify chunk kernel instead (``lens`` is
        then the post-append length)."""
        if self.tp_axis is not None:
            from attention_tpu.parallel.serving import head_sharded_decode

            return head_sharded_decode(
                q1, kr, vc, lens, mesh=self.mesh,
                axis_name=self.tp_axis, **kw,
            )
        if q1.ndim == 4:
            from attention_tpu.ops.decode import flash_decode_chunk

            return flash_decode_chunk(q1, kr, vc, lens, **kw)
        return flash_decode(q1, kr, vc, lens, **kw)

    def _batch_flash_call(self, q, k, v, **kw):
        """The batch flash kernel for cached prefill / chunked append —
        head-sharded over ``tp_axis`` (`serving.head_sharded_prefill`),
        local otherwise."""
        if self.tp_axis is None:
            return flash_attention(q, k, v, **kw)
        from attention_tpu.parallel.serving import head_sharded_prefill

        return head_sharded_prefill(q, k, v, mesh=self.mesh,
                                    axis_name=self.tp_axis, **kw)

    def _cached_attention(self, q, k, v, cache: KVCache):
        """Append S new KV rows at ``cache.length``, attend over the
        valid prefix.  ``impl='flash'``: S == 1 -> fused flash-decode
        kernel; S > 1 (prefill, or chunked prefill appending to history)
        -> the flash kernel with a dynamic ``q_offset``/``kv_valid``
        window.  ``impl='xla'``: masked dense einsums that XLA
        auto-partitions under mesh shardings (sharded serving)."""
        s_new = q.shape[2]
        capacity = cache.k.shape[2]
        kc = jax.lax.dynamic_update_slice(
            cache.k, k.astype(cache.k.dtype), (0, 0, cache.length, 0)
        )
        vc = jax.lax.dynamic_update_slice(
            cache.v, v.astype(cache.v.dtype), (0, 0, cache.length, 0)
        )
        new_len = cache.length + s_new
        # Cached dispatch is explicit per impl: a registry entry without a
        # cached path must fail loudly, not silently take the flash one.
        if self.impl not in ("xla", "flash"):
            raise KeyError(
                f"impl {self.impl!r} has no cached-attention path "
                f"(supported: ['flash', 'xla'])"
            )
        # Single-token decode on a RoPE'd sink model reads the sink keys
        # re-rotated to their in-cache positions (see _sink_read_keys);
        # chunked appends (s_new > 1) keep absolute rotations — the
        # per-query shift is not uniform there, and chunked decode on a
        # sink model is a prefill-style operation anyway.
        kr = kc
        if (self.rope and self.attn_sinks and self.window is not None
                and s_new == 1):
            kr = _sink_read_keys(kc, new_len, self.window, self.attn_sinks,
                                 self.rope_theta)
        if self.impl == "xla":
            out = _xla_cached_attention(
                q, kr, vc, start=cache.length, new_len=new_len,
                causal=self.causal, window=self.window,
                softcap=self.softcap, sinks=self.attn_sinks,
            )
        elif s_new == 1:
            # windowed decode included: the decode kernel's per-sequence
            # [len-w, len) band + pinned sinks clamps out-of-window block
            # DMAs, so bandwidth scales with the window, not the prefix
            out = self._decode_call(
                q[:, :, 0, :], kr, vc, new_len,
                softcap=self.softcap, window=self.window,
                sinks=self.attn_sinks or None)[:, :, None, :]
        else:
            # chunked prefill / multi-token append: the banded flash
            # kernel applies the window over the cache
            out = self._batch_flash_call(
                q, kr, vc, causal=self.causal,
                q_offset=cache.length, kv_valid=new_len, window=self.window,
                softcap=self.softcap,
                sinks=self.attn_sinks or None,
            )
        # Overflowing the cache would silently clamp the write index
        # (dynamic_update_slice semantics) and corrupt attention; make it
        # loud instead — poison the output with NaN.
        out = jnp.where(new_len <= capacity, out, jnp.nan).astype(out.dtype)
        return out, KVCache(kc, vc, new_len)

    def _rolling_attention(self, q, k, v, cache: RollingKVCache):
        """Bounded-memory sliding-window (+sinks) serving on the ring
        buffer — see `RollingKVCache` for the slot layout.

        S == 1 (decode): write the new row at its slot (pinned for the
        first ``sinks`` tokens, ring otherwise) and attend over the
        valid slots with the fused decode kernel (slot order is
        irrelevant to softmax).  S > 1 (prefill) assumes a FRESH cache:
        the chunk attends only to itself (causal + window + sinks);
        the first ``sinks`` and last ``window`` rows seed the buffer.
        """
        if self.impl != "flash":
            raise ValueError(
                f"impl {self.impl!r} has no rolling-cache path "
                "(supported: ['flash'])"
            )
        if self.window is None:
            raise ValueError("RollingKVCache requires a windowed model")
        sinks = self.attn_sinks
        ring = self.window
        expect_cap = RollingKVCache.capacity_for(ring, sinks)
        if cache.capacity != expect_cap:
            raise ValueError(
                f"rolling capacity {cache.capacity} != expected "
                f"{expect_cap} (window {ring} + sinks {sinks}, rounded "
                "to the 128-slot granule)"
            )
        s_new = q.shape[2]
        zero = jnp.zeros((), jnp.int32)
        if s_new == 1:
            t = cache.length
            # pinned sink slots [0, sinks); ring slots [sinks, sinks+ring)
            slot = jnp.where(
                t < sinks, t, sinks + jnp.mod(t - sinks, ring)
            ) if sinks else jnp.mod(t, ring)
            kc = jax.lax.dynamic_update_slice(
                cache.k, k.astype(cache.k.dtype), (0, 0, slot, 0)
            )
            vc = jax.lax.dynamic_update_slice(
                cache.v, v.astype(cache.v.dtype), (0, 0, slot, 0)
            )
            valid = jnp.minimum(cache.length + 1, sinks + ring)
            kr = kc
            if self.rope and sinks:
                kr = _sink_read_keys(kc, cache.length + 1, ring, sinks,
                                     self.rope_theta)
            out = self._decode_call(q[:, :, 0, :], kr, vc, valid,
                                    softcap=self.softcap)[:, :, None, :]
        else:
            # fresh-cache prefill: the chunk sees only itself.  A
            # non-fresh cache would silently drop in-window history, so
            # poison that case loudly (the convention of this module).
            out = self._batch_flash_call(q, k, v, causal=True,
                                         window=self.window,
                                         softcap=self.softcap,
                                         sinks=sinks or None)
            out = jnp.where(cache.length == 0, out, jnp.nan).astype(out.dtype)
            kc, vc = cache.k, cache.v
            sink_keep = min(s_new, sinks)
            if sink_keep:
                kc = jax.lax.dynamic_update_slice(
                    kc, k[:, :, :sink_keep].astype(kc.dtype),
                    (zero, zero, zero, zero),
                )
                vc = jax.lax.dynamic_update_slice(
                    vc, v[:, :, :sink_keep].astype(vc.dtype),
                    (zero, zero, zero, zero),
                )
            keep = min(max(s_new - sinks, 0), ring)
            if keep:
                # ring rows land rotated so the invariant 'slot(t) =
                # sinks + (t - sinks) % ring' holds; split is static
                # (fresh cache): 1-2 contiguous writes, no scatter
                rows_k = k[:, :, s_new - keep:].astype(kc.dtype)
                rows_v = v[:, :, s_new - keep:].astype(vc.dtype)
                split = (s_new - keep - sinks) % ring
                first = ring - split
                kc = jax.lax.dynamic_update_slice(
                    kc, rows_k[:, :, :first],
                    (zero, zero, jnp.int32(sinks + split), zero),
                )
                vc = jax.lax.dynamic_update_slice(
                    vc, rows_v[:, :, :first],
                    (zero, zero, jnp.int32(sinks + split), zero),
                )
                if split:
                    kc = jax.lax.dynamic_update_slice(
                        kc, rows_k[:, :, first:],
                        (zero, zero, jnp.int32(sinks), zero),
                    )
                    vc = jax.lax.dynamic_update_slice(
                        vc, rows_v[:, :, first:],
                        (zero, zero, jnp.int32(sinks), zero),
                    )
        return out, RollingKVCache(kc, vc, cache.length + s_new)

    def _ragged_attention(self, q, k, v, cache: RaggedKVCache):
        """S == 1: one decode step per sequence at per-sequence
        positions.  S > 1: a speculative-verify chunk append — S rows
        written at each sequence's length, scored causally in one cache
        stream (`ops.decode.flash_decode_chunk`)."""
        if self.impl != "flash":
            raise ValueError(
                f"impl {self.impl!r} has no ragged-cache path "
                "(supported: ['flash'])"
            )
        s_new = q.shape[2]
        write = jax.vmap(
            lambda buf, rows, i: jax.lax.dynamic_update_slice(
                buf, rows, (jnp.int32(0), i, jnp.int32(0))
            )
        )
        kc = write(cache.k, k.astype(cache.k.dtype), cache.lengths)
        vc = write(cache.v, v.astype(cache.v.dtype), cache.lengths)
        new_lengths = cache.lengths + s_new
        # Sliding-window serving on the ragged cache: each query sits at
        # its own len-1, so the decode kernel's per-sequence [len-w, len)
        # band (+ pinned sinks) applies directly; with RoPE the sink
        # re-rotation delta is per-sequence.  Chunk appends keep
        # absolute rotations (the dense path's rule for s_new > 1).
        kr = kc
        if (self.rope and self.attn_sinks and self.window is not None
                and s_new == 1):
            kr = _sink_read_keys(kc, new_lengths, self.window,
                                 self.attn_sinks, self.rope_theta)
        if s_new == 1:
            out = self._decode_call(
                q[:, :, 0, :], kr, vc, new_lengths, softcap=self.softcap,
                window=self.window, sinks=self.attn_sinks or None,
            )[:, :, None, :]
        else:
            out = self._decode_call(
                q, kr, vc, new_lengths, softcap=self.softcap,
                window=self.window, sinks=self.attn_sinks or None,
            )
        # per-sequence overflow poison (same loud-overflow contract)
        over = new_lengths > cache.k.shape[2]
        out = jnp.where(over[:, None, None, None], jnp.nan, out)
        return out.astype(q.dtype), RaggedKVCache(kc, vc, new_lengths)

    def _ragged_paged_step(self, q, k, v, cache: RaggedPagedStep):
        """One packed serving step: every request's tokens for this
        step — one per decode, a chunk per prefill — ride a single
        token axis and lower onto ONE ragged kernel launch (append
        through the per-slot page tables, then
        `ops.ragged_paged.ragged_paged_attention`)."""
        if self.impl != "flash":
            raise ValueError(
                f"impl {self.impl!r} has no ragged paged-step path "
                "(supported: ['flash'])"
            )
        if self.rope and self.attn_sinks and self.window is not None:
            raise ValueError(
                "rope+sinks needs the per-sequence rotated sink read "
                "copy (paged_sink_decode), which the packed step does "
                "not carry; generate_paged runs such a model one "
                "request at a time"
            )
        if self.tp_axis is not None:
            # head-sharded single-launch step: append + ragged
            # attention run per KV-head shard inside one shard_map
            # (pools and new rows shard, host-packed indices
            # replicate) — the mesh serving engine's ragged lowering
            from attention_tpu.parallel.serving import (
                head_sharded_ragged_step,
            )

            out, cache = head_sharded_ragged_step(
                q, cache, k, v, mesh=self.mesh, axis_name=self.tp_axis,
                softcap=self.softcap, window=self.window,
                sinks=self.attn_sinks or None,
            )
            return out.astype(q.dtype), cache
        cache = ragged_paged_append(cache, k, v)
        out = ragged_paged_attention(
            q, cache, softcap=self.softcap, window=self.window,
            sinks=self.attn_sinks or None,
        )
        return out.astype(q.dtype), cache

    def _paged_attention(self, q, k, v, cache: PagedKV):
        """S == 1: one decode step per sequence through the page table.
        S > 1: a speculative-verify chunk append (rows written through
        the table row-by-row, scored causally in one pool stream)."""
        if self.impl != "flash":
            raise ValueError(
                f"impl {self.impl!r} has no paged-cache path "
                "(supported: ['flash'])"
            )
        s_new = q.shape[2]
        if s_new > 1:
            from attention_tpu.ops.paged import paged_append_chunk

            cache = paged_append_chunk(cache, k, v)
            if self.tp_axis is not None:
                from attention_tpu.parallel.serving import (
                    head_sharded_decode_paged,
                )

                out = head_sharded_decode_paged(
                    q, cache, mesh=self.mesh, axis_name=self.tp_axis,
                    softcap=self.softcap, window=self.window,
                    sinks=self.attn_sinks or None,
                )
            else:
                # rope+sinks chunk appends keep absolute rotations (the
                # dense path's s_new > 1 rule), so no sink read copy
                out = paged_flash_decode(
                    q, cache, softcap=self.softcap,
                    window=self.window, sinks=self.attn_sinks or None,
                )
            return out.astype(q.dtype), cache
        cache = paged_append(cache, k, v)
        if self.rope and self.attn_sinks and self.window is not None:
            if self.tp_axis is not None:
                raise ValueError(
                    "rope+sinks on the paged cache reads a per-sequence "
                    "rotated sink copy (paged_sink_decode), which has no "
                    "head-sharded form yet; serve rope+sink models "
                    "tensor-parallel on the dense/ragged/int8 caches"
                )
            # in-cache sink re-rotation can't touch pool pages (they may
            # be prefix-shared across sequences with different deltas);
            # paged_sink_decode instead rotates a per-sequence READ COPY
            # of the sink rows and merges it with the window band — the
            # int8 cache's sink_read_rotation pattern applied at page
            # read
            from attention_tpu.ops.paged import paged_sink_decode

            out = paged_sink_decode(
                q[:, :, 0, :], cache, window=self.window,
                sinks=self.attn_sinks, theta=self.rope_theta,
                softcap=self.softcap,
            )[:, :, None, :]
        elif self.tp_axis is not None:
            from attention_tpu.parallel.serving import (
                head_sharded_decode_paged,
            )

            out = head_sharded_decode_paged(
                q[:, :, 0, :], cache, mesh=self.mesh,
                axis_name=self.tp_axis, softcap=self.softcap,
                window=self.window, sinks=self.attn_sinks or None,
            )[:, :, None, :]
        else:
            out = paged_flash_decode(
                q[:, :, 0, :], cache, softcap=self.softcap,
                window=self.window, sinks=self.attn_sinks or None,
            )[:, :, None, :]
        return out.astype(q.dtype), cache

    def _quantized_decode(self, q, k, v, cache: QuantKVCache):
        """One decode step against an int8 cache: quantize the new KV
        row in, run the fused quantized kernel.  Prefill runs on the
        bf16 `KVCache`, then `KVCache.quantize()` converts.  S > 1 is a
        speculative-verify chunk: rows quantize-append, then score
        causally in one int8 stream
        (`ops.quant.flash_decode_quantized_chunk`)."""
        if self.impl != "flash":
            raise ValueError(
                f"impl {self.impl!r} has no quantized-cache path "
                "(supported: ['flash'])"
            )
        s_new = q.shape[2]
        if s_new > 1:
            kv = update_quantized_kv(cache.kv, k, v, cache.length)
            new_len = cache.length + s_new
            if self.tp_axis is not None:
                from attention_tpu.parallel.serving import (
                    head_sharded_decode_quantized,
                )

                out = head_sharded_decode_quantized(
                    q, kv, new_len, mesh=self.mesh,
                    axis_name=self.tp_axis, softcap=self.softcap,
                    window=self.window, sinks=self.attn_sinks or None)
            else:
                from attention_tpu.ops.quant import (
                    flash_decode_quantized_chunk,
                )

                out = flash_decode_quantized_chunk(
                    q, kv, new_len, softcap=self.softcap,
                    window=self.window, sinks=self.attn_sinks or None)
            return out.astype(q.dtype), QuantKVCache(kv, new_len)
        kv = update_quantized_kv(cache.kv, k, v, cache.length)
        new_len = cache.length + 1
        kr = kv
        if self.rope and self.attn_sinks and self.window is not None:
            # int8 counterpart of _sink_read_keys (per-sequence storage,
            # so — unlike paged pool pages — re-rotation is legal)
            kr = sink_read_rotation(kv, new_len, self.window,
                                    self.attn_sinks, self.rope_theta)
        if self.tp_axis is not None:
            from attention_tpu.parallel.serving import (
                head_sharded_decode_quantized,
            )

            out = head_sharded_decode_quantized(
                q[:, :, 0, :], kr, new_len, mesh=self.mesh,
                axis_name=self.tp_axis, softcap=self.softcap,
                window=self.window, sinks=self.attn_sinks or None)
        else:
            out = flash_decode_quantized(q[:, :, 0, :], kr, new_len,
                                         softcap=self.softcap,
                                         window=self.window,
                                         sinks=self.attn_sinks or None)
        # overflow already NaN-poisons via update_quantized_kv's scales
        return out[:, :, None, :].astype(q.dtype), QuantKVCache(kv, new_len)
