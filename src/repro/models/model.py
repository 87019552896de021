"""Family-dispatching model assembly: describe / train-forward / prefill /
decode for every assigned architecture.

All layer stacks use ``jax.lax.scan`` over stacked parameters so compile time
and HLO size stay O(1) in depth (MaxText-style); decode caches are dense slot
buffers ``[L, B, S_max, ...]`` updated in place (JetStream-style — the TPU
adaptation of paged GPU caches, see DESIGN.md §2).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import kv_quant
from repro.models.config import ModelConfig
from repro.models.layers import (
    NULL_CTX,
    apply_dense_block,
    apply_dense_block_paged,
    apply_ffn,
    apply_mamba_block,
    apply_shared_block,
    blockwise_attention,
    decode_attention,
    describe_attention,
    describe_dense_block,
    describe_mamba_block,
    describe_shared_block,
    rmsnorm,
    softcap,
    stack,
    _project_qkv,
    _write_slot,
)
from repro.models.params import Leaf

F32 = jnp.float32
KV_AXES = ("layers", "batch", "kv_seq", "kv_heads_act", "head_dim")


def _maybe_remat(fn, enabled: bool):
    if not enabled:
        return fn
    return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)


class Model:
    """Functional model for one :class:`ModelConfig`."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # ------------------------------------------------------------ describe
    def describe(self) -> dict:
        cfg = self.cfg
        d, v = cfg.d_model, cfg.vocab_size
        tree: dict = {
            "embed": Leaf((v, d), ("vocab", "embed"), scale=0.02),
            "ln_f": Leaf((d,), ("embed_act",), init="zeros"),
            "head": Leaf((d, v), ("embed", "vocab")),
        }
        if cfg.family in ("dense", "moe", "vlm"):
            tree["blocks"] = stack(describe_dense_block(cfg), cfg.num_layers)
        elif cfg.family == "ssm":
            tree["blocks"] = stack(describe_mamba_block(cfg), cfg.num_layers)
        elif cfg.family == "hybrid":
            groups = cfg.num_layers // cfg.shared_attn_period
            tree["blocks"] = stack(
                stack(describe_mamba_block(cfg), cfg.shared_attn_period), groups
            )
            tree["shared"] = describe_shared_block(cfg)
        elif cfg.family == "encdec":
            tree["enc_blocks"] = stack(describe_dense_block(cfg), cfg.encoder_layers)
            dec = describe_dense_block(cfg)
            dec["lnx"] = Leaf((d,), ("embed_act",), init="zeros")
            dec["cross"] = describe_attention(cfg)
            tree["blocks"] = stack(dec, cfg.num_layers)
            tree["enc_ln_f"] = Leaf((d,), ("embed_act",), init="zeros")
        else:
            raise ValueError(cfg.family)
        if cfg.local_global_alternating and cfg.family != "encdec":
            # gemma2: scan over (local, global) pairs
            pair = {
                "local": describe_dense_block(cfg),
                "global": describe_dense_block(cfg),
            }
            tree["blocks"] = stack(pair, cfg.num_layers // 2)
        return tree

    # --------------------------------------------------------------- cache
    def describe_cache(self, batch: int, max_seq: int) -> dict:
        cfg = self.cfg
        L, KH, HD = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim

        def kv(layers, seq, kh, hd):
            return {
                "k": Leaf((layers, batch, seq, kh, hd), KV_AXES, jnp.bfloat16,
                          init="zeros"),
                "v": Leaf((layers, batch, seq, kh, hd), KV_AXES, jnp.bfloat16,
                          init="zeros"),
            }

        if cfg.family in ("dense", "moe", "vlm"):
            if cfg.local_global_alternating:
                half = L // 2
                return {"local": kv(half, max_seq, KH, HD),
                        "global": kv(half, max_seq, KH, HD)}
            return kv(L, max_seq, KH, HD)
        if cfg.family == "ssm":
            return self._ssm_cache((L,), batch)
        if cfg.family == "hybrid":
            groups = L // cfg.shared_attn_period
            c = self._ssm_cache((groups, cfg.shared_attn_period), batch)
            c.update(
                {
                    "shared_"
                    + k: Leaf(
                        (groups, batch, max_seq, cfg.num_kv_heads, cfg.hybrid_head_dim),
                        KV_AXES,
                        jnp.bfloat16,
                        init="zeros",
                    )
                    for k in ("k", "v")
                }
            )
            return c
        if cfg.family == "encdec":
            c = kv(L, max_seq, KH, HD)
            c.update(
                {
                    "ck": Leaf((L, batch, cfg.encoder_seq, KH, HD), KV_AXES,
                               jnp.bfloat16, init="zeros"),
                    "cv": Leaf((L, batch, cfg.encoder_seq, KH, HD), KV_AXES,
                               jnp.bfloat16, init="zeros"),
                }
            )
            return c
        raise ValueError(cfg.family)

    def _ssm_cache(self, lead: tuple[int, ...], batch: int) -> dict:
        cfg = self.cfg
        conv_dim = cfg.ssm_inner + 2 * cfg.ssm_state
        lead_axes = tuple("layers" for _ in lead)
        return {
            "ssm": Leaf(
                (*lead, batch, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
                (*lead_axes, "batch", "ssm_heads_act", None, None),
                F32,
                init="zeros",
            ),
            "conv": Leaf(
                (*lead, batch, cfg.ssm_conv_width - 1, conv_dim),
                (*lead_axes, "batch", None, "ssm_heads_act"),
                jnp.bfloat16,
                init="zeros",
            ),
        }

    # ------------------------------------------------------- sequence mode
    def sequence(self, params, x, positions, ctx=NULL_CTX, collect_cache=False,
                 frames=None, prefix=None):
        """Run the full stack over a token-embedded sequence ``x`` [B,S,d].

        ``prefix``: optional {"k","v"} [L,B,Sp,KH,HD] radix-cached KV for
        suffix prefill (dense families only). Returns
        (hidden, cache_tree_or_None, aux_loss).
        """
        cfg = self.cfg
        enc_out = None
        if cfg.family == "encdec":
            enc_out = self._encode(params, frames, ctx)

        if cfg.family in ("dense", "moe", "vlm") and not cfg.local_global_alternating:

            def body(h, xs):
                p, pre = xs
                h, kv, aux = apply_dense_block(
                    p, h, cfg, positions=positions, window=cfg.sliding_window,
                    prefix=pre, ctx=ctx,
                )
                return h, (kv if collect_cache else None, aux)

            body = _maybe_remat(body, cfg.remat)
            pre_xs = (prefix["k"], prefix["v"]) if prefix is not None else None
            x, (kvs, auxs) = jax.lax.scan(body, x, (params["blocks"], pre_xs))
            cache = {"k": kvs[0], "v": kvs[1]} if collect_cache else None
            return x, cache, jnp.sum(auxs)

        if cfg.local_global_alternating and cfg.family != "encdec":

            def body(h, p):
                h, kv_l, aux1 = apply_dense_block(
                    p["local"], h, cfg, positions=positions,
                    window=cfg.sliding_window, ctx=ctx,
                )
                h, kv_g, aux2 = apply_dense_block(
                    p["global"], h, cfg, positions=positions, window=None, ctx=ctx
                )
                out = ((kv_l, kv_g) if collect_cache else None, aux1 + aux2)
                return h, out

            body = _maybe_remat(body, cfg.remat)
            x, (kvs, auxs) = jax.lax.scan(body, x, params["blocks"])
            cache = None
            if collect_cache:
                (lk, lv), (gk, gv) = kvs
                cache = {"local": {"k": lk, "v": lv}, "global": {"k": gk, "v": gv}}
            return x, cache, jnp.sum(auxs)

        if cfg.family == "ssm":

            def body(h, p):
                h, st = apply_mamba_block(p, h, cfg, ctx=ctx)
                return h, (st if collect_cache else None)

            body = _maybe_remat(body, cfg.remat)
            x, sts = jax.lax.scan(body, x, params["blocks"])
            cache = {"ssm": sts[0], "conv": sts[1]} if collect_cache else None
            return x, cache, jnp.zeros((), F32)

        if cfg.family == "hybrid":
            x0 = x

            def group(h, p):
                def inner(hh, pp):
                    hh, st = apply_mamba_block(pp, hh, cfg, ctx=ctx)
                    return hh, (st if collect_cache else None)

                h, sts = jax.lax.scan(inner, h, p)
                h, kv = apply_shared_block(
                    params["shared"], h, x0, cfg, positions=positions, ctx=ctx
                )
                return h, (sts, kv if collect_cache else None)

            group = _maybe_remat(group, cfg.remat)
            x, (sts, kvs) = jax.lax.scan(group, x, params["blocks"])
            cache = None
            if collect_cache:
                cache = {
                    "ssm": sts[0],
                    "conv": sts[1],
                    "shared_k": kvs[0],
                    "shared_v": kvs[1],
                }
            return x, cache, jnp.zeros((), F32)

        if cfg.family == "encdec":

            def body(h, p):
                h, kv, ckv, aux = self._decoder_block(
                    p, h, enc_out, positions, ctx, cache=None
                )
                return h, ((kv, ckv) if collect_cache else None, aux)

            body = _maybe_remat(body, cfg.remat)
            x, (kvs, auxs) = jax.lax.scan(body, x, params["blocks"])
            cache = None
            if collect_cache:
                (k, v), (ck, cv) = kvs
                cache = {"k": k, "v": v, "ck": ck, "cv": cv}
            return x, cache, jnp.sum(auxs)

        raise ValueError(cfg.family)

    def _encode(self, params, frames, ctx):
        cfg = self.cfg
        pos = jnp.arange(frames.shape[1])[None, :]

        def body(h, p):
            h, _, aux = apply_dense_block(
                p, h, cfg, positions=pos, causal=False, ctx=ctx
            )
            return h, aux

        body = _maybe_remat(body, cfg.remat)
        h, _ = jax.lax.scan(body, frames.astype(jnp.bfloat16), params["enc_blocks"])
        return rmsnorm(h, params["enc_ln_f"])

    def _decoder_block(self, p, h, enc_out, positions, ctx, cache, lengths=None):
        """whisper decoder block: self-attn + cross-attn + ffn."""
        cfg = self.cfg
        H, KH, HD = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        if cache is None:
            h, kv, aux = _self_attn_only(p, h, cfg, positions, ctx)
            # cross attention against encoder output
            xq = rmsnorm(h, p["lnx"])
            q = (xq @ p["cross"]["wq"]).reshape(*xq.shape[:2], H, HD)
            ck = (enc_out @ p["cross"]["wk"]).reshape(
                enc_out.shape[0], enc_out.shape[1], KH, HD
            )
            cv = (enc_out @ p["cross"]["wv"]).reshape(
                enc_out.shape[0], enc_out.shape[1], KH, HD
            )
            a = blockwise_attention(q, ck, cv, causal=False, ctx=ctx)
            a = a.reshape(*xq.shape[:2], H * HD)
            h = h + a @ p["cross"]["wo"]
            h = h + apply_ffn(p["ffn"], rmsnorm(h, p["ln2"]), ctx)
            h = ctx.constrain(h, ("batch", "seq", "embed_act"))
            return h, kv, (ck, cv), aux
        else:
            (k_cache, v_cache, ck, cv) = cache
            h, (k_cache, v_cache), aux = _self_attn_only(
                p, h, cfg, positions, ctx, cache=(k_cache, v_cache), lengths=lengths
            )
            xq = rmsnorm(h, p["lnx"])
            q = (xq @ p["cross"]["wq"]).reshape(xq.shape[0], H, HD)
            enc_len = jnp.full((xq.shape[0],), ck.shape[1], jnp.int32)
            a = decode_attention(q, ck, cv, lengths=enc_len, ctx=ctx)[:, None, :]
            h = h + a @ p["cross"]["wo"]
            h = h + apply_ffn(p["ffn"], rmsnorm(h, p["ln2"]), ctx)
            return h, (k_cache, v_cache), (ck, cv), aux

    # ----------------------------------------------------------- decode
    def decode(self, params, cache, tokens, lengths, ctx=NULL_CTX):
        """One decode step. tokens [B] int32; lengths [B] = context length
        including the new token. Returns (logits [B,V], new_cache)."""
        cfg = self.cfg
        x = jnp.take(params["embed"], tokens, axis=0)[:, None, :]  # [B,1,d]
        positions = (lengths - 1)[:, None]

        if cfg.family in ("dense", "moe", "vlm") and not cfg.local_global_alternating:

            def body(h, xs):
                p, k, v = xs
                h, (k, v), _ = apply_dense_block(
                    p, h, cfg, positions=positions, window=cfg.sliding_window,
                    cache=(k, v), lengths=lengths, ctx=ctx,
                )
                return h, (k, v)

            x, (ks, vs) = jax.lax.scan(body, x, (params["blocks"], cache["k"], cache["v"]))
            new_cache = {"k": ks, "v": vs}

        elif cfg.local_global_alternating:
            # ring-buffer local cache when its slot count < the global cache's
            local_slots = cache["local"]["k"].shape[2]          # [L/2,B,S,KH,HD]
            ring = local_slots if local_slots < cache["global"]["k"].shape[2] else None

            def body(h, xs):
                p, lk, lv, gk, gv = xs
                h, (lk, lv), _ = apply_dense_block(
                    p["local"], h, cfg, positions=positions,
                    window=cfg.sliding_window, cache=(lk, lv), lengths=lengths,
                    ring_window=ring, ctx=ctx,
                )
                h, (gk, gv), _ = apply_dense_block(
                    p["global"], h, cfg, positions=positions, window=None,
                    cache=(gk, gv), lengths=lengths, ctx=ctx,
                )
                return h, (lk, lv, gk, gv)

            x, (lks, lvs, gks, gvs) = jax.lax.scan(
                body,
                x,
                (
                    params["blocks"],
                    cache["local"]["k"],
                    cache["local"]["v"],
                    cache["global"]["k"],
                    cache["global"]["v"],
                ),
            )
            new_cache = {
                "local": {"k": lks, "v": lvs},
                "global": {"k": gks, "v": gvs},
            }

        elif cfg.family == "ssm":

            def body(h, xs):
                p, st, cv = xs
                h, (st, cv) = apply_mamba_block(p, h, cfg, cache=(st, cv), ctx=ctx)
                return h, (st, cv)

            x, (sts, cvs) = jax.lax.scan(
                body, x, (params["blocks"], cache["ssm"], cache["conv"])
            )
            new_cache = {"ssm": sts, "conv": cvs}

        elif cfg.family == "hybrid":
            x0 = x

            def group(h, xs):
                p, st, cv, sk, sv = xs

                def inner(hh, pp_s):
                    pp, st1, cv1 = pp_s
                    hh, (st1, cv1) = apply_mamba_block(
                        pp, hh, cfg, cache=(st1, cv1), ctx=ctx
                    )
                    return hh, (st1, cv1)

                h, (st, cv) = jax.lax.scan(inner, h, (p, st, cv))
                h, (sk, sv) = apply_shared_block(
                    params["shared"], h, x0, cfg, positions=positions,
                    cache=(sk, sv), lengths=lengths, ctx=ctx,
                )
                return h, (st, cv, sk, sv)

            x, (sts, cvs, sks, svs) = jax.lax.scan(
                group,
                x,
                (
                    params["blocks"],
                    cache["ssm"],
                    cache["conv"],
                    cache["shared_k"],
                    cache["shared_v"],
                ),
            )
            new_cache = {"ssm": sts, "conv": cvs, "shared_k": sks, "shared_v": svs}

        elif cfg.family == "encdec":

            def body(h, xs):
                p, k, v, ck, cv = xs
                h, (k, v), (ck, cv), _ = self._decoder_block(
                    p, h, None, positions, ctx, cache=(k, v, ck, cv), lengths=lengths
                )
                return h, (k, v, ck, cv)

            x, (ks, vs, cks, cvs) = jax.lax.scan(
                body, x, (params["blocks"], cache["k"], cache["v"], cache["ck"], cache["cv"])
            )
            new_cache = {"k": ks, "v": vs, "ck": cks, "cv": cvs}
        else:
            raise ValueError(cfg.family)

        h = rmsnorm(x[:, 0, :], params["ln_f"])
        logits = softcap((h @ params["head"]).astype(F32), cfg.final_logit_softcap)
        logits = ctx.constrain(logits, ("batch", "vocab_act"))
        return logits, new_cache

    # ------------------------------------------------------- paged decode
    def decode_paged(
        self, params, k_pages, v_pages, tokens, lengths, block_tables,
        tail_pages, tail_offsets, k_scales=None, v_scales=None, ctx=NULL_CTX,
    ):
        """One block-table decode step (dense-cache families only).

        The paged twin of :meth:`decode`: the KV cache is the serving
        engine's ``PagePool`` arrays ``k_pages``/``v_pages``
        ``[L, N, T, KH*HD]`` — not a per-slot dense buffer — and each
        sequence reads its context through ``block_tables`` ``[B, P]``.
        The pool is the layer scan's *carry*: layer ``l`` writes the new
        token's KV (global position ``lengths[b] - 1``) at ``(l,
        tail_pages[b], tail_offsets[b])`` in place and its attention reads
        the carried pool at ``l``, so with input donation a decode step
        copies neither the pool nor a layer slice of it. Layers scan
        exactly like :meth:`decode` so compile stays O(1) in depth.

        On an int8-resident pool, pass the per-(layer, page) scale
        sidecars ``k_scales``/``v_scales`` ``[L, N]``: they ride the carry
        too, and each layer's write becomes a requantize-insert of its
        tail pages (their scales may grow to admit the new token).
        Returns ``(logits [B, V], k_pages', v_pages')`` — plus
        ``(k_scales', v_scales')`` when sidecars were passed.
        """
        cfg = self.cfg
        assert cfg.family in ("dense", "moe", "vlm") and (
            not cfg.local_global_alternating
        ), "paged decode serves the dense-cache families"
        x = jnp.take(params["embed"], tokens, axis=0)[:, None, :]  # [B,1,d]

        def body(carry, xs):
            h, kp, vp, ks, vs = carry
            p, layer = xs
            h, pool, _ = apply_dense_block_paged(
                p, h, cfg, layer=layer, k_pages=kp, v_pages=vp,
                block_tables=block_tables, tail_pages=tail_pages,
                tail_offsets=tail_offsets, lengths=lengths, k_scales=ks,
                v_scales=vs, window=cfg.sliding_window, ctx=ctx,
            )
            return (h, *pool), None

        layers = jnp.arange(cfg.num_layers, dtype=jnp.int32)
        (x, k_pages, v_pages, k_scales, v_scales), _ = jax.lax.scan(
            body, (x, k_pages, v_pages, k_scales, v_scales),
            (params["blocks"], layers),
        )
        h = rmsnorm(x[:, 0, :], params["ln_f"])
        logits = softcap((h @ params["head"]).astype(F32), cfg.final_logit_softcap)
        logits = ctx.constrain(logits, ("batch", "vocab_act"))
        if k_scales is not None:
            return logits, k_pages, v_pages, k_scales, v_scales
        return logits, k_pages, v_pages

    def prefill_paged(
        self, params, k_pages, v_pages, tokens, prefix_idx, write_idx,
        prefix_valid, pos0, take, logit_index, k_scales=None, v_scales=None,
        ctx=NULL_CTX,
    ):
        """One chunk of prefill into a paged pool (dense-cache families).

        The paged twin of :meth:`prefill` with a radix-cached prefix: the
        pool ``[L, N, T, KH*HD]`` is the layer scan's carry. Layer ``l``
        gathers its prefix pages ``pool[l, prefix_idx]`` (padded to a page
        bucket; positions from ``prefix_valid`` on are masked), attends
        the chunk ``tokens`` ``[1, C]`` (absolute positions from ``pos0``)
        over prefix + chunk, and writes the chunk's KV into its pages
        ``pool[l, write_idx]`` in place. Chunk positions from ``take`` on
        are written as zeros, so a partial tail page holds what a whole
        prefill would leave there. Nothing pool- or layer-sized is copied.

        On an int8-resident pool the gather dequantizes through the scale
        sidecars ``k_scales``/``v_scales`` ``[L, N]`` and each written page
        is quantized with a fresh scale. Returns ``(logits [1, V],
        k_pages', v_pages')`` — plus ``(k_scales', v_scales')`` when
        sidecars were passed.
        """
        cfg = self.cfg
        assert cfg.family in ("dense", "moe", "vlm") and (
            not cfg.local_global_alternating
        ), "paged prefill serves the dense-cache families"
        x = jnp.take(params["embed"], tokens, axis=0)               # [1,C,d]
        C = x.shape[1]
        positions = pos0 + jnp.arange(C)[None, :]
        x = ctx.constrain(x, ("batch", "seq", "embed_act"))
        kh, hd = cfg.num_kv_heads, cfg.head_dim
        T = k_pages.shape[2]
        n_write = write_idx.shape[0]
        keep = (jnp.arange(n_write * T) < take)[:, None]
        quantized = k_scales is not None

        def gather(pages, scales, layer):
            got = pages[layer, prefix_idx]                          # [n,T,F]
            if quantized:
                got = kv_quant.dequantize_pages(
                    got, scales[layer, prefix_idx], jnp.bfloat16
                )
            return got.reshape(1, -1, kh, hd)

        def write(pages, scales, layer, kv):
            kv = kv[0].reshape(C, kh * hd)
            kv = jnp.pad(kv, ((0, n_write * T - C), (0, 0)))
            kv = jnp.where(keep, kv, 0).reshape(n_write, T, kh * hd)
            if quantized:
                kv, s = kv_quant.quantize_pages(kv)
                scales = scales.at[layer, write_idx].set(s)
            return pages.at[layer, write_idx].set(kv.astype(pages.dtype)), scales

        def body(carry, xs):
            h, kp, vp, ks, vs = carry
            p, layer = xs
            prefix, valid = None, None
            # shape branch is deliberate bucketing: prefix_idx is padded to
            # the table bucket, so this compiles once per bucket
            if prefix_idx.shape[0]:  # lint: jit-shape-branch-ok
                prefix = (gather(kp, ks, layer), gather(vp, vs, layer))
                valid = prefix_valid
            h, (k, v), _ = apply_dense_block(
                p, h, cfg, positions=positions, window=cfg.sliding_window,
                prefix=prefix, prefix_valid=valid, ctx=ctx,
            )
            kp, ks = write(kp, ks, layer, k)
            vp, vs = write(vp, vs, layer, v)
            return (h, kp, vp, ks, vs), None

        layers = jnp.arange(cfg.num_layers, dtype=jnp.int32)
        (x, k_pages, v_pages, k_scales, v_scales), _ = jax.lax.scan(
            body, (x, k_pages, v_pages, k_scales, v_scales),
            (params["blocks"], layers),
        )
        h = rmsnorm(x[:, logit_index, :], params["ln_f"])
        logits = softcap((h @ params["head"]).astype(F32), cfg.final_logit_softcap)
        if quantized:
            return logits, k_pages, v_pages, k_scales, v_scales
        return logits, k_pages, v_pages

    # ------------------------------------------------------------ prefill
    def prefill(self, params, batch: dict, ctx=NULL_CTX, prefix=None,
                logit_index=None):
        """Full- or suffix-context forward; returns (last_logits, cache).

        With ``prefix`` (stacked radix-cached KV), only ``batch["tokens"]``
        (the suffix) is computed, attending over prefix+suffix. The
        returned cache covers the suffix only. (The dense-slot engine
        prefills this way; the paged engine prefills into its pool through
        :meth:`prefill_paged`.)

        ``logit_index`` names the *token* position whose logits to return
        (default: the last). The serving engine pads suffixes to a fixed
        bucket so prefill compiles once per bucket instead of once per
        length — causality guarantees positions at or before
        ``logit_index`` never see the padding.
        """
        cfg = self.cfg
        tokens = batch["tokens"]
        x = jnp.take(params["embed"], tokens, axis=0)
        n_img = 0
        if cfg.family == "vlm":
            img = batch["image_embeds"].astype(x.dtype)
            n_img = img.shape[1]
            x = jnp.concatenate([img, x], axis=1)
        S = x.shape[1]
        q_off = 0 if prefix is None else prefix["k"].shape[2]
        positions = q_off + jnp.arange(S)[None, :]
        x = ctx.constrain(x, ("batch", "seq", "embed_act"))
        h, cache, _ = self.sequence(
            params, x, positions, ctx, collect_cache=True,
            frames=batch.get("frames"), prefix=prefix,
        )
        idx = -1 if logit_index is None else n_img + logit_index
        h = rmsnorm(h[:, idx, :], params["ln_f"])
        logits = softcap((h @ params["head"]).astype(F32), cfg.final_logit_softcap)
        return logits, cache

    # -------------------------------------------------------------- train
    def loss(self, params, batch: dict, ctx=NULL_CTX):
        cfg = self.cfg
        tokens = batch["tokens"]                               # [B, S+1]
        inputs, labels = tokens[:, :-1], tokens[:, 1:]
        x = jnp.take(params["embed"], inputs, axis=0)
        n_img = 0
        if cfg.family == "vlm":
            img = batch["image_embeds"].astype(x.dtype)
            n_img = img.shape[1]
            x = jnp.concatenate([img, x], axis=1)
        S = x.shape[1]
        positions = jnp.arange(S)[None, :]
        x = ctx.constrain(x, ("batch", "seq", "embed_act"))
        h, _, aux = self.sequence(
            params, x, positions, ctx, collect_cache=False,
            frames=batch.get("frames"),
        )
        if n_img:
            h = h[:, n_img:, :]
        h = rmsnorm(h, params["ln_f"])
        logits = softcap((h @ params["head"]).astype(F32), cfg.final_logit_softcap)
        logits = ctx.constrain(logits, ("batch", "seq", "vocab_act"))
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        ce = (logz - gold).mean()
        return ce + 0.01 * aux, {"ce": ce, "aux": aux}


def _self_attn_only(p, h, cfg, positions, ctx, cache=None, lengths=None):
    """The attention half of a dense block (used by the whisper decoder)."""
    H, KH, HD = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    a_in = rmsnorm(h, p["ln1"])
    q, k, v = _project_qkv(p["attn"], a_in, H, KH, HD, positions, cfg.rope_theta, ctx=ctx)
    if cache is None:
        a = blockwise_attention(q, k, v, causal=True, ctx=ctx)
        a = a.reshape(*h.shape[:2], H * HD)
        h = h + a @ p["attn"]["wo"]
        return h, (k, v), jnp.zeros((), F32)
    k_cache, v_cache = cache
    idx = lengths - 1
    k_cache = _write_slot(k_cache, k[:, 0], idx)
    v_cache = _write_slot(v_cache, v[:, 0], idx)
    a = decode_attention(q[:, 0], k_cache, v_cache, lengths=lengths, ctx=ctx)[:, None, :]
    h = h + a @ p["attn"]["wo"]
    return h, (k_cache, v_cache), jnp.zeros((), F32)
