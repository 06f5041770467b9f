"""Attention mixers: GQA/MQA, sliding-window (local), and MLA (DeepSeek).

Design notes (TPU):
* Train/prefill attention is CHUNKED with an online softmax expressed in
  lax.scan — the same math as the Pallas flash kernel
  (repro.kernels.flash_attention) but lowerable by plain XLA, so the compiled
  dry-run never materializes an S×S buffer. On real TPUs the Pallas kernel is
  the fast path (impl switch at the step level).
* GQA uses the grouped formulation (B, KV, G, S, D) — no materialized
  head-expansion of K/V.
* Sliding-window attention uses neighbor-chunk pairing: with chunk size W a
  query chunk attends exactly (its own + previous) chunk ⇒ O(S·2W) FLOPs,
  static shapes, no gather.
* MLA keeps the compressed cache (c_kv, k_rope) and expands K/V per KV-chunk
  inside the scan (prefill) or runs fully absorbed in the compressed space
  (decode) — cache is rank·S instead of 2·H·D·S.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist import annotate
from repro.models.lm.common import apply_rope

# a host scalar: a jnp constant here would start a JAX backend at import
NEG_INF = np.float32(-1e30)


def pick_chunk(s: int, target: int) -> int:
    """Largest divisor of s that is ≤ target (chunked scans need s % c == 0;
    odd lengths like S−1=4095 for MTP or prefix+text=4352 for VLMs occur)."""
    c = min(target, s)
    while s % c != 0:
        c -= 1
    return max(c, 1)


# ---------------------------------------------------------------- chunked core
def _online_softmax_step(carry, kv_chunk, q, q_pos, k_pos_chunk, scale,
                         causal, window, softcap=0.0):
    """One KV-chunk update. q: (B, KV, G, Sq, D); kv_chunk: (k, v) each
    (B, KV, Ck, D[v]); positions broadcastable."""
    m_prev, l_prev, acc = carry
    k, v = kv_chunk
    s = jnp.einsum("bkgqd,bkcd->bkgqc", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if softcap > 0.0:
        s = softcap * jnp.tanh(s / softcap)
    mask = jnp.ones(s.shape[-2:], bool)
    if causal:
        mask &= k_pos_chunk[None, :] <= q_pos[:, None]
    if window > 0:
        mask &= k_pos_chunk[None, :] > q_pos[:, None] - window
    s = jnp.where(mask, s, NEG_INF)
    m_cur = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_cur[..., None])
    alpha = jnp.exp(m_prev - m_cur)
    l_cur = l_prev * alpha + p.sum(axis=-1)
    acc = acc * alpha[..., None] + jnp.einsum(
        "bkgqc,bkcd->bkgqd", p, v.astype(jnp.float32))
    return (m_cur, l_cur, acc), None


def chunked_attention(
    q: jnp.ndarray,           # (B, S, H, D)
    k: jnp.ndarray,           # (B, Sk, KV, D)
    v: jnp.ndarray,           # (B, Sk, KV, Dv)
    causal: bool = True,
    window: int = 0,
    chunk_k: int = 1024,
    scale: Optional[float] = None,
    q_offset: int = 0,
    softcap: float = 0.0,
) -> jnp.ndarray:
    """Online-softmax attention, scanning KV chunks. Returns (B, S, H, Dv)."""
    b, sq, h, d = q.shape
    sk, kv = k.shape[1], k.shape[2]
    g = h // kv
    dv = v.shape[-1]
    scale = scale if scale is not None else d ** -0.5
    ck = pick_chunk(sk, chunk_k)
    nk = sk // ck

    qg = q.reshape(b, sq, kv, g, d).transpose(0, 2, 3, 1, 4)   # (B,KV,G,S,D)
    kc = k.transpose(0, 2, 1, 3).reshape(b, kv, nk, ck, d).transpose(2, 0, 1, 3, 4)
    vc = v.transpose(0, 2, 1, 3).reshape(b, kv, nk, ck, dv).transpose(2, 0, 1, 3, 4)
    q_pos = q_offset + jnp.arange(sq)
    k_pos = jnp.arange(sk).reshape(nk, ck)

    m0 = jnp.full((b, kv, g, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kv, g, sq), jnp.float32)
    a0 = jnp.zeros((b, kv, g, sq, dv), jnp.float32)

    def body(carry, xs):
        kch, vch, kp = xs
        return _online_softmax_step(carry, (kch, vch), qg, q_pos, kp, scale,
                                    causal, window, softcap)

    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kc, vc, k_pos))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, dv)
    return out.astype(q.dtype)


def sliding_window_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, window: int,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Causal sliding-window attention in O(S·2W): chunk size = W, each query
    chunk attends (previous, own) chunks only. q (B,S,H,D), k/v (B,S,KV,D)."""
    b, s, h, d = q.shape
    kv = k.shape[2]
    dv = v.shape[-1]
    g = h // kv
    scale = scale if scale is not None else d ** -0.5
    if s <= window:   # degenerate: plain causal
        return chunked_attention(q, k, v, causal=True, window=window,
                                 chunk_k=min(s, 1024), scale=scale)
    w = window
    assert s % w == 0, f"seq {s} % window {w}"
    nc = s // w
    qg = q.reshape(b, nc, w, kv, g, d)
    kc = k.reshape(b, nc, w, kv, d)
    vc = v.reshape(b, nc, w, kv, dv)
    # previous chunk (zero-padded for the first)
    k_prev = jnp.pad(kc[:, :-1], ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))
    v_prev = jnp.pad(vc[:, :-1], ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))
    k2 = jnp.concatenate([k_prev, kc], axis=2)     # (B,nc,2W,KV,D)
    v2 = jnp.concatenate([v_prev, vc], axis=2)
    s_ = jnp.einsum("bnqkgd,bnckd->bnkgqc", qg.astype(jnp.float32),
                    k2.astype(jnp.float32)) * scale
    q_pos = jnp.arange(w)[:, None]                  # within-pair positions
    k_pos = jnp.arange(2 * w)[None, :] - w
    mask = (k_pos <= q_pos) & (k_pos > q_pos - w)
    first_mask = mask & (k_pos >= 0)                # first chunk has no prev
    full_mask = jnp.broadcast_to(mask, (nc,) + mask.shape).at[0].set(first_mask)
    s_ = jnp.where(full_mask[None, :, None, None], s_, NEG_INF)
    p = jax.nn.softmax(s_, axis=-1)
    out = jnp.einsum("bnkgqc,bnckd->bnqkgd", p, v2.astype(jnp.float32))
    return out.reshape(b, s, h, dv).astype(q.dtype)


# ------------------------------------------------------------------- GQA mixer
def gqa_params_shape(cfg):
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    shapes = {
        "wq": (d, h * hd), "wk": (d, kv * hd), "wv": (d, kv * hd),
        "wo": (h * hd, d),
    }
    if cfg.qkv_bias:
        shapes.update({"bq": (h * hd,), "bk": (kv * hd,), "bv": (kv * hd,)})
    return shapes


def gqa_forward(cfg, p: Dict, x: jnp.ndarray, positions: jnp.ndarray,
                window: int = 0, chunk_k: int = 1024) -> jnp.ndarray:
    """Full-sequence (train/prefill). x: (B, S, D_model)."""
    b, s, _ = x.shape
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    # SP→TP transition: gather the sequence ONCE and let q/k/v share it
    # (§Perf B5 — without the explicit constraint XLA materializes three
    # separate full-seq all-gathers per pass).
    x = annotate(x, "batch", None, "embed")
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, h, hd)
    k = k.reshape(b, s, kv, hd)
    v = v.reshape(b, s, kv, hd)
    if cfg.pos_embed == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    q = annotate(q, "batch", "seq", "heads", None)
    k = annotate(k, "batch", "seq", "kv_heads", None)
    if window > 0:
        out = sliding_window_attention(q, k, v, window)
    else:
        out = chunked_attention(q, k, v, causal=True, chunk_k=chunk_k,
                                softcap=cfg.logit_softcap)
    out = annotate(out, "batch", "seq", "heads", None)
    return out.reshape(b, s, h * hd) @ p["wo"]


def gqa_decode(cfg, p: Dict, x: jnp.ndarray, cache: Dict, pos: jnp.ndarray,
               window: int = 0) -> Tuple[jnp.ndarray, Dict]:
    """Single-token decode. x: (B, 1, D). cache: k/v (B, S_max, KV, hd)
    (ring buffer of size `window` for local layers). pos: scalar int32 —
    absolute position of the new token."""
    b = x.shape[0]
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, 1, h, hd)
    k = k.reshape(b, 1, kv, hd)
    v = v.reshape(b, 1, kv, hd)
    if cfg.pos_embed == "rope":
        q = apply_rope(q, pos[None], cfg.rope_theta)
        k = apply_rope(k, pos[None], cfg.rope_theta)
    s_max = cache["k"].shape[1]
    slot = jnp.where(window > 0, pos % s_max, jnp.minimum(pos, s_max - 1))
    ck = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                      (0, slot, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                      (0, slot, 0, 0))
    ck_a = annotate(ck, "batch", "cache_seq", "kv_heads", None)
    cv_a = annotate(cv, "batch", "cache_seq", "kv_heads", None)
    # positions of cache slots
    idx = jnp.arange(s_max)
    if window > 0:
        # ring: slot i holds absolute position pos - ((slot - i) mod s_max)
        abs_pos = pos - ((slot - idx) % s_max)
        valid = (abs_pos >= 0) & (abs_pos >= pos - window + 1) & (abs_pos <= pos)
    else:
        abs_pos = idx
        valid = idx <= pos
    qg = q.reshape(b, 1, kv, h // kv, hd).transpose(0, 2, 3, 1, 4)
    s_ = jnp.einsum("bkgqd,bskd->bkgqs", qg.astype(jnp.float32),
                    ck_a.astype(jnp.float32)) * (hd ** -0.5)
    if cfg.logit_softcap > 0:
        s_ = cfg.logit_softcap * jnp.tanh(s_ / cfg.logit_softcap)
    s_ = jnp.where(valid[None, None, None, None, :], s_, NEG_INF)
    pr = jax.nn.softmax(s_, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bkgqd", pr, cv_a.astype(jnp.float32))
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, 1, h * hd).astype(x.dtype)
    return out @ p["wo"], {"k": ck, "v": cv}


def gqa_cache_shape(cfg, batch: int, s_max: int, window: int = 0):
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    size = min(window, s_max) if window > 0 else s_max
    return {"k": (batch, size, kv, hd), "v": (batch, size, kv, hd)}


# ------------------------------------------------------------------- MLA mixer
def mla_params_shape(cfg):
    d = cfg.d_model
    h = cfg.num_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    r_kv, r_q = cfg.kv_lora_rank, cfg.q_lora_rank
    shapes = {
        "wkv_a": (d, r_kv + dr),
        "kv_norm": (r_kv,),
        "wk_b": (r_kv, h * dn),
        "wv_b": (r_kv, h * dv),
        "wo": (h * dv, d),
    }
    if r_q:
        shapes.update({"wq_a": (d, r_q), "q_norm": (r_q,),
                       "wq_b": (r_q, h * (dn + dr))})
    else:
        shapes.update({"wq": (d, h * (dn + dr))})
    return shapes


def _mla_q(cfg, p, x, positions):
    b, s, _ = x.shape
    h = cfg.num_heads
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    if cfg.q_lora_rank:
        from repro.models.lm.common import rms_norm
        q = rms_norm(x @ p["wq_a"], p["q_norm"], cfg.norm_eps) @ p["wq_b"]
    else:
        q = x @ p["wq"]
    q = q.reshape(b, s, h, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def mla_forward(cfg, p: Dict, x: jnp.ndarray, positions: jnp.ndarray,
                chunk_k: int = 1024) -> jnp.ndarray:
    """Prefill/train MLA. K/V are expanded from the compressed cache PER
    KV-CHUNK inside the scan, so the expanded (S, H, D) tensors never exist
    at full length — HBM peak stays O(S·rank + chunk·H·D)."""
    from repro.models.lm.common import rms_norm
    b, s, _ = x.shape
    h = cfg.num_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    r_kv = cfg.kv_lora_rank

    # SP→TP: one shared full-seq gather for q/kv projections (§Perf B5)
    x = annotate(x, "batch", None, "embed")
    kv_a = x @ p["wkv_a"]                               # (B,S,r+dr)
    c_kv = rms_norm(kv_a[..., :r_kv], p["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(kv_a[..., None, r_kv:], positions, cfg.rope_theta)  # (B,S,1,dr)

    q_nope, q_rope = _mla_q(cfg, p, x, positions)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)       # (B,S,H,dn+dr)
    q = annotate(q, "batch", "seq", "heads", None)

    ck = pick_chunk(s, chunk_k)
    nk = s // ck
    scale = (dn + dr) ** -0.5
    qg = q.transpose(0, 2, 1, 3)[:, None]                # (B,1,H,S,dn+dr)
    q_pos = jnp.arange(s)

    c_chunks = c_kv.reshape(b, nk, ck, r_kv).transpose(1, 0, 2, 3)
    r_chunks = k_rope.reshape(b, nk, ck, dr).transpose(1, 0, 2, 3)
    k_pos = jnp.arange(s).reshape(nk, ck)

    m0 = jnp.full((b, 1, h, s), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, 1, h, s), jnp.float32)
    a0 = jnp.zeros((b, 1, h, s, dv), jnp.float32)

    wk_b = p["wk_b"].reshape(r_kv, h, dn)
    wv_b = p["wv_b"].reshape(r_kv, h, dv)

    def body2(carry, xs):
        m_prev, l_prev, acc = carry
        cc, rc, kp = xs
        k_nope = jnp.einsum("bcr,rhd->bhcd", cc.astype(jnp.float32),
                            wk_b.astype(jnp.float32))
        v_full = jnp.einsum("bcr,rhd->bhcd", cc.astype(jnp.float32),
                            wv_b.astype(jnp.float32))
        s_n = jnp.einsum("bhqd,bhcd->bhqc", qg[:, 0, :, :, :dn].astype(jnp.float32), k_nope)
        s_r = jnp.einsum("bhqd,bcd->bhqc", qg[:, 0, :, :, dn:].astype(jnp.float32),
                         rc.astype(jnp.float32))
        s_ = (s_n + s_r) * scale
        mask = kp[None, :] <= q_pos[:, None]
        s_ = jnp.where(mask[None, None], s_, NEG_INF)
        m_cur = jnp.maximum(m_prev[:, 0], s_.max(axis=-1))
        pr = jnp.exp(s_ - m_cur[..., None])
        alpha = jnp.exp(m_prev[:, 0] - m_cur)
        l_cur = l_prev[:, 0] * alpha + pr.sum(axis=-1)
        acc_new = acc[:, 0] * alpha[..., None] + jnp.einsum("bhqc,bhcd->bhqd", pr, v_full)
        return (m_cur[:, None], l_cur[:, None], acc_new[:, None]), None

    (m, l, acc), _ = jax.lax.scan(body2, (m0, l0, a0), (c_chunks, r_chunks, k_pos))
    out = acc[:, 0] / jnp.maximum(l[:, 0], 1e-30)[..., None]   # (B,H,S,dv)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, h * dv).astype(x.dtype)
    out = annotate(out, "batch", "seq", None)
    return out @ p["wo"]


def mla_decode(cfg, p: Dict, x: jnp.ndarray, cache: Dict, pos: jnp.ndarray
               ) -> Tuple[jnp.ndarray, Dict]:
    """Absorbed MLA decode: all work in the compressed space.
    cache: c_kv (B, S_max, r_kv), k_rope (B, S_max, dr)."""
    from repro.models.lm.common import rms_norm
    b = x.shape[0]
    h = cfg.num_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    r_kv = cfg.kv_lora_rank

    kv_a = x @ p["wkv_a"]
    c_new = rms_norm(kv_a[..., :r_kv], p["kv_norm"], cfg.norm_eps)
    kr_new = apply_rope(kv_a[..., None, r_kv:], pos[None], cfg.rope_theta)[:, :, 0]

    cc = jax.lax.dynamic_update_slice(cache["c_kv"], c_new.astype(cache["c_kv"].dtype),
                                      (0, pos, 0))
    cr = jax.lax.dynamic_update_slice(cache["k_rope"], kr_new.astype(cache["k_rope"].dtype),
                                      (0, pos, 0))

    q_nope, q_rope = _mla_q(cfg, p, x, pos[None])        # (B,1,H,dn/dr)
    wk_b = p["wk_b"].reshape(r_kv, h, dn)
    wv_b = p["wv_b"].reshape(r_kv, h, dv)
    q_abs = jnp.einsum("bqhd,rhd->bqhr", q_nope.astype(jnp.float32),
                       wk_b.astype(jnp.float32))          # (B,1,H,r_kv)

    cc_a = annotate(cc, "batch", "cache_seq", None)
    cr_a = annotate(cr, "batch", "cache_seq", None)
    s_n = jnp.einsum("bqhr,bsr->bhqs", q_abs, cc_a.astype(jnp.float32))
    s_r = jnp.einsum("bqhd,bsd->bhqs", q_rope.astype(jnp.float32),
                     cr_a.astype(jnp.float32))
    s_ = (s_n + s_r) * ((dn + dr) ** -0.5)
    valid = jnp.arange(cc.shape[1]) <= pos
    s_ = jnp.where(valid[None, None, None], s_, NEG_INF)
    pr = jax.nn.softmax(s_, axis=-1)
    ctx = jnp.einsum("bhqs,bsr->bqhr", pr, cc_a.astype(jnp.float32))   # (B,1,H,r)
    out = jnp.einsum("bqhr,rhd->bqhd", ctx, wv_b.astype(jnp.float32))  # (B,1,H,dv)
    out = out.reshape(b, 1, h * dv).astype(x.dtype)
    return out @ p["wo"], {"c_kv": cc, "k_rope": cr}


def mla_cache_shape(cfg, batch: int, s_max: int):
    return {"c_kv": (batch, s_max, cfg.kv_lora_rank),
            "k_rope": (batch, s_max, cfg.qk_rope_head_dim)}
