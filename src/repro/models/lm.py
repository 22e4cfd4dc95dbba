"""Unified decoder-only LM covering dense / GQA / MoE / hybrid / SSM archs.

A config lists a repeating ``block_pattern`` (e.g. ``("rglru", "rglru",
"local")`` for Griffin-style hybrids); the stack scans over *pattern units*
with layer-stacked parameters, so HLO size is O(|pattern|), not O(depth) —
essential for compile times at 1000+-chip scale and 60+-layer models.

Serving uses an int8-quantized KV cache and the paper's integerized
attention/linear path when ``cfg.quant.mode == "int"``.

In-place KV ring-cache contract (decode): the cache stores k/v exactly as
attention consumes them — int8 codes with per-tensor ``k_scale``/``v_scale``
(or uint8 nibble-packed int4 when ``kv_bits == 4``) in a ring of ``span``
slots where position ``p`` lives at slot ``p % span``.  Each decode step
writes the new key/value into its slot and hands the *whole stored ring*
to :func:`repro.layers.attention.attention` as a ``QTensor`` plus the
``k_positions`` slot->position map (negative = unwritten).  Nothing is
unpacked or dequantized here: the Pallas decode kernel reads the packed
ring in place and streams only live blocks; only the XLA fallback unpacks.

Paged KV-cache contract (continuous batching, :func:`init_paged_cache`):
instead of per-batch rings, every attention layer owns shared
``(num_pages + 1, Hkv, page_size, hd[/2])`` page pools (the extra last
page is the TRASH page — all masked/unallocated writes land there and it
is never read), and the cache top level carries per-sequence state:
``pos (B,)`` (negative = inactive row) and ``page_table (B, max_pages)``
(sequence b's logical page l lives in physical page ``page_table[b, l]``;
negative = unallocated).  Scales calibrate per sequence ``(B,)`` but are
READ per PHYSICAL page (``page_k_scale``/``page_v_scale`` pools, written
at prefill for every reserved page): a hot sequence can never re-scale
another tenant's cached codes, and a page aliased from a shared prefix
dequantizes with its OWNER's grid wherever it is read.  Logical
position p of a sequence lives at page ``p // page_size``, row
``p % page_size`` — the slot->position map of the ring becomes implicit.
Ragged prefill (``batch["lengths"]``) writes each row's own pages and
masks pad positions to the trash page; decode writes one row per sequence
at its own ``pos[b]`` and attends through
:func:`repro.layers.attention.paged_attention`, which streams only that
sequence's live pages.  :func:`admission_prefill` batches W ragged
admissions through ONE such prefill on a shared-pool view of the serving
cache: codes land directly at the reserved physical pages (no private
batch=1 cache, no page-copy pass) and, because every activation grid is
per sequence, each admitted row is bit-identical to a solo prefill.  Page
allocation/recycling policy lives in :mod:`repro.launch.engine` — this
module only reads/writes what the page table names.

Prefix sharing (``prefix_len`` through :func:`forward` /
:func:`paged_prefill` / :func:`admission_prefill`): a prompt whose first
``prefix_len`` positions are already cached — its own prefix chunk, or a
prefix SHARED from another sequence's pages — prefills only the tail; the
tail attends the prefix through its cached codes on the pages' stored
grids (:func:`repro.layers.attention.prefix_prefill_attention`), which is
what makes a shared prefix bit-identical to a privately prefilled one.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.api import QuantConfig, dense, integerize_params
from repro.core.quant import QTensor
from repro.layers import moe as moe_lib
from repro.layers.attention import (AttnSpec, attention, paged_attention,
                                    prefix_prefill_attention)
from repro.layers.embed import embed_lookup, init_embed
from repro.layers.mlp import init_mlp, mlp
from repro.layers.moe import MoEConfig
from repro.layers.norms import apply_norm, init_norm
from repro.layers.rglru import init_rglru, init_rglru_state, rglru_block
from repro.layers.rope import apply_rope
from repro.layers.ssd import SSDConfig, init_ssd, init_ssd_state, ssd_block
from repro.distributed.sharding import shard
from repro.models.scan_util import scan as _scan


@dataclasses.dataclass(frozen=True)
class LMConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None
    block_pattern: tuple = ("attn",)          # cycled; "attn"|"local"|"rglru"|"ssd"
    attn_window: Optional[int] = None
    rope_theta: float = 10000.0
    rotary_frac: float = 1.0                  # chatglm "2d": 0.5
    qkv_bias: bool = False
    norm: str = "rmsnorm"
    act: str = "swiglu"
    moe: Optional[MoEConfig] = None
    ssd: Optional[SSDConfig] = None
    d_rnn: Optional[int] = None
    dtype: str = "bfloat16"
    quant: Optional[QuantConfig] = None
    q_chunk: int = 128
    loss_chunk: int = 512
    remat: bool = True
    frontend: Optional[str] = None            # "patch" (VLM stub)
    n_patches: int = 256
    causal: bool = True

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def jdtype(self):
        return jnp.bfloat16 if self.dtype == "bfloat16" else jnp.float32

    def replace(self, **kw) -> "LMConfig":
        return dataclasses.replace(self, **kw)


def block_kinds(cfg: LMConfig) -> list[str]:
    pat = cfg.block_pattern
    return [pat[i % len(pat)] for i in range(cfg.n_layers)]


def unit_structure(cfg: LMConfig):
    """(unit kinds, n_units, remainder kinds)."""
    unit = tuple(cfg.block_pattern)
    n_units = cfg.n_layers // len(unit)
    rem = tuple(block_kinds(cfg)[n_units * len(unit):])
    return unit, n_units, rem


# ---------------------------------------------------------------------------
# Block init
# ---------------------------------------------------------------------------

def _lin(key, din, dout, dtype, bias=False):
    p = {"w": (jax.random.normal(key, (din, dout)) * din ** -0.5).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((dout,), dtype)
    return p

def init_attn(key, cfg: LMConfig) -> dict:
    ks = jax.random.split(key, 4)
    d, hd = cfg.d_model, cfg.hd
    dt = cfg.jdtype
    return {
        "wq": _lin(ks[0], d, cfg.n_heads * hd, dt, cfg.qkv_bias),
        "wk": _lin(ks[1], d, cfg.kv_heads * hd, dt, cfg.qkv_bias),
        "wv": _lin(ks[2], d, cfg.kv_heads * hd, dt, cfg.qkv_bias),
        "wo": _lin(ks[3], cfg.n_heads * hd, d, dt),
    }


def init_block(key, cfg: LMConfig, kind: str) -> dict:
    ks = jax.random.split(key, 4)
    d, dt = cfg.d_model, cfg.jdtype
    p = {"ln1": init_norm(d, cfg.norm)}
    if kind in ("attn", "local"):
        p["attn"] = init_attn(ks[0], cfg)
    elif kind == "rglru":
        p["rglru"] = init_rglru(ks[0], d, cfg.d_rnn or d, dt)
    elif kind == "ssd":
        p["ssd"] = init_ssd(ks[0], d, cfg.ssd, dt)
    else:
        raise ValueError(kind)
    if cfg.d_ff > 0:
        p["ln2"] = init_norm(d, cfg.norm)
        if cfg.moe is not None:
            p["ffn"] = moe_lib.init_moe(ks[1], d, cfg.d_ff, cfg.moe,
                                        act=cfg.act, dtype=dt)
        else:
            p["ffn"] = init_mlp(ks[1], d, cfg.d_ff, act=cfg.act, dtype=dt)
    return p


def _init_unit(key, cfg: LMConfig) -> dict:
    unit = tuple(cfg.block_pattern)
    kk = jax.random.split(key, len(unit))
    return {f"b{j}": init_block(kk[j], cfg, kind)
            for j, kind in enumerate(unit)}


def _init_top(ks, cfg: LMConfig) -> dict:
    return {"embed": init_embed(ks[0], cfg.vocab, cfg.d_model, cfg.jdtype),
            "final_norm": init_norm(cfg.d_model, cfg.norm),
            "lm_head": _lin(ks[1], cfg.d_model, cfg.vocab, cfg.jdtype)}


def init_params(key, cfg: LMConfig) -> dict:
    unit, n_units, rem = unit_structure(cfg)
    ks = jax.random.split(key, 4 + len(rem))
    params = _init_top(ks, cfg)
    if n_units:
        unit_keys = jax.random.split(ks[2], n_units)
        params["units"] = jax.vmap(lambda k: _init_unit(k, cfg))(unit_keys)
    for i, kind in enumerate(rem):
        params[f"rem{i}"] = init_block(ks[4 + i], cfg, kind)
    return params


@functools.partial(jax.jit, static_argnums=(1, 2))
def init_int_params(key, cfg: LMConfig, qc: QuantConfig) -> dict:
    """Serving parameters at any width, without the float model.

    Equals ``integerize_params(init_params(key, cfg), qc)`` bit for bit, but
    initialises and integerises one pattern unit at a time (``lax.map``
    over the unit keys), so the device holds the integer codes and scales
    plus a single unit's float weights — ChatGLM3-6B's float model alone
    would not fit one 16 GB chip.
    """
    unit, n_units, rem = unit_structure(cfg)
    ks = jax.random.split(key, 4 + len(rem))
    params = integerize_params(_init_top(ks, cfg), qc)
    if n_units:
        params["units"] = jax.lax.map(
            lambda k: integerize_params(_init_unit(k, cfg), qc),
            jax.random.split(ks[2], n_units))
    for i, kind in enumerate(rem):
        params[f"rem{i}"] = integerize_params(
            init_block(ks[4 + i], cfg, kind), qc)
    return params


# ---------------------------------------------------------------------------
# KV / recurrent cache
# ---------------------------------------------------------------------------

def _attn_cache(cfg: LMConfig, batch: int, max_len: int) -> dict:
    mode = cfg.quant.mode if cfg.quant else "float"
    if mode == "int" and cfg.quant.kv_bits == 4:
        # nibble-packed int4 cache: half the HBM read per decode step.
        shape = (batch, cfg.kv_heads, max_len, cfg.hd // 2)
        c = {"k": jnp.zeros(shape, jnp.uint8),
             "v": jnp.zeros(shape, jnp.uint8),
             "k_scale": jnp.ones((), jnp.float32),
             "v_scale": jnp.ones((), jnp.float32)}
        return c
    kv_dt = jnp.int8 if mode == "int" else cfg.jdtype
    shape = (batch, cfg.kv_heads, max_len, cfg.hd)
    c = {"k": jnp.zeros(shape, kv_dt), "v": jnp.zeros(shape, kv_dt)}
    if mode == "int":
        c["k_scale"] = jnp.ones((), jnp.float32)
        c["v_scale"] = jnp.ones((), jnp.float32)
    return c


def init_block_cache(cfg: LMConfig, kind: str, batch: int, max_len: int):
    if kind in ("attn", "local"):
        span = max_len if (kind == "attn" or cfg.attn_window is None) \
            else min(max_len, cfg.attn_window + cfg.q_chunk)
        return _attn_cache(cfg, batch, span)
    if kind == "rglru":
        return init_rglru_state(batch, cfg.d_rnn or cfg.d_model)
    if kind == "ssd":
        return init_ssd_state(batch, cfg.d_model, cfg.ssd)
    raise ValueError(kind)


def init_cache(cfg: LMConfig, batch: int, max_len: int) -> dict:
    unit, n_units, rem = unit_structure(cfg)
    cache = {"pos": jnp.zeros((), jnp.int32)}
    if n_units:
        def one(_):
            return {f"b{j}": init_block_cache(cfg, kind, batch, max_len)
                    for j, kind in enumerate(unit)}
        cache["units"] = jax.vmap(one)(jnp.arange(n_units))
    for i, kind in enumerate(rem):
        cache[f"rem{i}"] = init_block_cache(cfg, kind, batch, max_len)
    return cache


# ---------------------------------------------------------------------------
# Paged KV cache (continuous batching)
# ---------------------------------------------------------------------------

def _paged_attn_cache(cfg: LMConfig, batch: int, num_pages: int,
                      page_size: int) -> dict:
    """Shared page pools (+1 trash page) with per-sequence (B,) scales.

    int mode additionally carries per-PHYSICAL-page scale pools
    ``page_k_scale``/``page_v_scale`` (num_pages + 1,): entry p is the
    dequantization step page p's codes were PREFILLED with.  Reads resolve
    scales through these pools, which is what makes physical-page sharing
    safe — a prefix page aliased into another sequence's table dequantizes
    with its owner's grid, and the (B,) per-sequence scales remain the
    calibration record (and the source the prefill scatters from).
    """
    mode = cfg.quant.mode if cfg.quant else "float"
    kv4 = mode == "int" and cfg.quant.kv_bits == 4
    dk = cfg.hd // 2 if kv4 else cfg.hd
    dt = jnp.uint8 if kv4 else (jnp.int8 if mode == "int" else cfg.jdtype)
    shape = (num_pages + 1, cfg.kv_heads, page_size, dk)
    c = {"k_pages": jnp.zeros(shape, dt), "v_pages": jnp.zeros(shape, dt)}
    if mode == "int":
        c["k_scale"] = jnp.ones((batch,), jnp.float32)
        c["v_scale"] = jnp.ones((batch,), jnp.float32)
        c["page_k_scale"] = jnp.ones((num_pages + 1,), jnp.float32)
        c["page_v_scale"] = jnp.ones((num_pages + 1,), jnp.float32)
    return c


def init_paged_cache(cfg: LMConfig, batch: int, max_len: int, *,
                     page_size: int = 32,
                     num_pages: Optional[int] = None) -> dict:
    """Paged serving cache: page pools per attention layer, shared tables.

    ``max_len`` bounds any single sequence (sets ``max_pages`` =
    page-table width); ``num_pages`` sizes the shared physical pool
    (default: no overcommit, ``batch * max_pages``).  All rows start
    inactive (``pos = -1``) with empty page tables; recurrent blocks keep
    their usual per-row states.
    """
    unit, n_units, rem = unit_structure(cfg)
    max_pages = -(-max_len // page_size)
    if num_pages is None:
        num_pages = batch * max_pages
    cache = {"pos": jnp.full((batch,), -1, jnp.int32),
             "page_table": jnp.full((batch, max_pages), -1, jnp.int32)}

    def blockc(kind):
        if kind in ("attn", "local"):
            return _paged_attn_cache(cfg, batch, num_pages, page_size)
        return init_block_cache(cfg, kind, batch, max_len)

    if n_units:
        def one(_):
            return {f"b{j}": blockc(kind) for j, kind in enumerate(unit)}
        cache["units"] = jax.vmap(one)(jnp.arange(n_units))
    for i, kind in enumerate(rem):
        cache[f"rem{i}"] = blockc(kind)
    return cache


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def _paged_write_decode(cache, k1, v1, positions, page_table, mode, qcfg):
    """Write one decoded key/value per sequence into its own page.

    k1, v1: (B, Hkv, hd).  Row b goes to physical page
    ``page_table[b, pos_b // page_size]`` at page row ``pos_b % page_size``;
    unallocated/inactive rows land in the trash page.  Codes are emitted on
    the TARGET PAGE's registered scale (``page_k_scale[phys]``): the
    prefill pre-registered every reserved page on the row's own grid, so
    this equals the old per-sequence scale for private rows — but a decode
    write that lands in a CoW'd partial boundary page keeps that page's
    (prefix owner's) grid, so one page never mixes two quantization grids.
    """
    pos = positions[:, 0]
    num_phys = cache["k_pages"].shape[0] - 1       # last page = trash
    ps = cache["k_pages"].shape[2]
    logical = jnp.clip(pos // ps, 0, page_table.shape[1] - 1)
    phys = jnp.take_along_axis(page_table, logical[:, None], axis=1)[:, 0]
    phys = jnp.where((phys >= 0) & (pos >= 0), phys, num_phys)
    row = jnp.mod(pos, ps)
    if mode == "int" and qcfg.kv_bits == 4:
        from repro.core.quant import pack_int4, qrange
        qmin, qmax = qrange(4)
        ks, vs = cache["page_k_scale"][phys], cache["page_v_scale"][phys]
        kq = pack_int4(jnp.clip(jnp.round(k1 / ks[:, None, None]),
                                qmin, qmax).astype(jnp.int8))
        vq = pack_int4(jnp.clip(jnp.round(v1 / vs[:, None, None]),
                                qmin, qmax).astype(jnp.int8))
    elif mode == "int":
        ks, vs = cache["page_k_scale"][phys], cache["page_v_scale"][phys]
        kq = jnp.round(k1 / ks[:, None, None]).astype(jnp.int8)
        vq = jnp.round(v1 / vs[:, None, None]).astype(jnp.int8)
    else:
        kq = k1.astype(cache["k_pages"].dtype)
        vq = v1.astype(cache["v_pages"].dtype)
    ck = cache["k_pages"].at[phys, :, row].set(kq)
    cv = cache["v_pages"].at[phys, :, row].set(vq)
    return dict(cache, k_pages=ck, v_pages=cv)


def _paged_write_prefill(cache, k, v, positions, lengths, page_table, mode,
                         qcfg, prefix_len: int = 0):
    """Scatter a whole (ragged) prompt's keys/values into per-row pages.

    k, v: (B, Hkv, S, hd) at absolute positions ``prefix_len + i``.  Row
    b's positions ``>= prefix_len + lengths[b]`` are pad: they are
    excluded from the per-sequence scale calibration and their writes land
    in the trash page.  Returns the cache with pools, per-sequence scales
    AND per-page scale registrations updated.

    Per-page scale registration (int mode): every allocated page-table
    entry from the first fully-owned page on (logical id
    ``>= ceil(prefix_len / page_size)`` — i.e. excluding shared prefix
    pages and a CoW'd partial boundary page, which keep the grids their
    prefix chunk registered) gets the row's fresh scale — including
    reserved-but-unwritten decode pages, so decode writes always find
    their page's grid.  Codes are then emitted on each position's TARGET
    PAGE's registered scale: identical to the per-sequence grid for
    private pages, the prefix owner's grid inside a shared boundary page.
    """
    b, _, s, _ = k.shape
    num_phys = cache["k_pages"].shape[0] - 1
    ps = cache["k_pages"].shape[2]
    lens = jnp.full((b,), s, jnp.int32) if lengths is None \
        else jnp.asarray(lengths, jnp.int32)
    valid = positions < (prefix_len + lens)[:, None]         # (B, S)
    logical = jnp.clip(positions // ps, 0, page_table.shape[1] - 1)
    phys = jnp.take_along_axis(page_table, logical, axis=1)    # (B, S)
    phys = jnp.where(valid & (phys >= 0), phys, num_phys)
    row = jnp.mod(positions, ps)
    new_cache = dict(cache)
    if mode == "int":
        from repro.core.quant import pack_int4, pin, qrange
        kv4 = qcfg.kv_bits == 4
        qmin, qmax = qrange(4) if kv4 else qrange(8)
        vmask = valid[:, None, :, None]

        def rowscale(t):
            amax = jnp.max(jnp.abs(t) * vmask, axis=(1, 2, 3))
            return pin(jnp.maximum(amax.astype(jnp.float32), 1e-8) / qmax)

        ksc, vsc = rowscale(k), rowscale(v)
        new_cache["k_scale"], new_cache["v_scale"] = ksc, vsc
        # Register the row's grid on every fully-owned allocated page.
        own_from = -(-prefix_len // ps)
        maxp = page_table.shape[1]
        ownable = (jnp.arange(maxp)[None, :] >= own_from) & (page_table >= 0)
        tgt = jnp.where(ownable, page_table, num_phys)
        pks = cache["page_k_scale"].at[tgt].set(
            jnp.broadcast_to(ksc[:, None], (b, maxp)))
        pvs = cache["page_v_scale"].at[tgt].set(
            jnp.broadcast_to(vsc[:, None], (b, maxp)))
        new_cache["page_k_scale"], new_cache["page_v_scale"] = pks, pvs
        # Emit codes on each position's target-page grid.
        kstep = pks[phys][:, None, :, None]                  # (B,1,S,1)
        vstep = pvs[phys][:, None, :, None]
        kq = jnp.clip(jnp.round(k / kstep), qmin, qmax).astype(jnp.int8)
        vq = jnp.clip(jnp.round(v / vstep), qmin, qmax).astype(jnp.int8)
        if kv4:
            kq, vq = pack_int4(kq), pack_int4(vq)
    else:
        kq = k.astype(cache["k_pages"].dtype)
        vq = v.astype(cache["v_pages"].dtype)
    upd_k = kq.transpose(0, 2, 1, 3)                           # (B,S,Hkv,dk)
    upd_v = vq.transpose(0, 2, 1, 3)
    new_cache["k_pages"] = cache["k_pages"].at[phys, :, row].set(upd_k)
    new_cache["v_pages"] = cache["v_pages"].at[phys, :, row].set(upd_v)
    return new_cache


def _attn_mixer(x, p, cfg: LMConfig, kind: str, positions, cache, decode,
                page_table=None, lengths=None, prefix_len: int = 0):
    b, s, _ = x.shape
    hd, hq, hkv = cfg.hd, cfg.n_heads, cfg.kv_heads
    qcfg = cfg.quant

    def split(y, h):
        # TP constraint on the flat feature dim (always divisible; head
        # counts often aren't a multiple of the TP degree).
        y = shard(y, "batch", None, "model")
        return y.reshape(b, s, h, hd).transpose(0, 2, 1, 3)

    q = split(dense(x, p["wq"], qcfg), hq)
    k = split(dense(x, p["wk"], qcfg), hkv)
    v = split(dense(x, p["wv"], qcfg), hkv)
    q = apply_rope(q, positions, theta=cfg.rope_theta,
                   rotary_frac=cfg.rotary_frac)
    k = apply_rope(k, positions, theta=cfg.rope_theta,
                   rotary_frac=cfg.rotary_frac)

    window = cfg.attn_window if kind == "local" else None
    spec = AttnSpec(causal=cfg.causal, window=window, q_chunk=cfg.q_chunk)
    mode = qcfg.mode if qcfg else "float"
    new_cache = cache
    paged = cache is not None and "k_pages" in cache

    if paged and decode:
        # Paged decode: write each row at its own position, then attend
        # through the per-sequence page tables (only that row's live pages
        # stream).  int mode resolves k/v scales PER PHYSICAL PAGE, so a
        # page shared from a prefix owner dequantizes on the owner's grid.
        new_cache = _paged_write_decode(cache, jnp.squeeze(k, 2),
                                        jnp.squeeze(v, 2), positions,
                                        page_table, mode, qcfg)
        ones = jnp.ones((b,), jnp.float32)
        out = paged_attention(q, new_cache["k_pages"], new_cache["v_pages"],
                              new_cache.get("k_scale", ones),
                              new_cache.get("v_scale", ones),
                              page_table, positions[:, 0], spec, qcfg,
                              k_page_scale=new_cache.get("page_k_scale"),
                              v_page_scale=new_cache.get("page_v_scale"))
    elif paged and prefix_len:
        # Tail-chunk prefill onto an already-cached prefix (prefix sharing):
        # the fresh tail attends the prefix THROUGH ITS CACHED CODES on the
        # pages' stored grids — never dequantized to float, never re-scaled
        # — then scatters only its own tail pages (pads -> trash).
        ps_ = cache["k_pages"].shape[2]
        npre = -(-prefix_len // ps_)
        num_phys = cache["k_pages"].shape[0] - 1
        from repro.kernels.ref import gather_pages
        k_pre = gather_pages(cache["k_pages"], page_table[:, :npre])
        v_pre = gather_pages(cache["v_pages"], page_table[:, :npre])
        if mode == "int":
            if k_pre.dtype == jnp.uint8:
                from repro.core.quant import unpack_int4
                k_pre, v_pre = unpack_int4(k_pre), unpack_int4(v_pre)
            idx = jnp.clip(page_table[:, :npre], 0, num_phys)
            pks, pvs = cache["page_k_scale"][idx], cache["page_v_scale"][idx]
        else:
            pks = pvs = None
        out = prefix_prefill_attention(q, k, v, k_pre, v_pre, pks, pvs,
                                       prefix_len, lengths, spec, qcfg)
        new_cache = _paged_write_prefill(cache, k, v, positions, lengths,
                                         page_table, mode, qcfg,
                                         prefix_len=prefix_len)
    elif paged:
        # Paged (ragged) prefill: attention over the fresh prompt is the
        # ordinary prefill path; the cache write scatters each row's keys
        # into its own pages (pad positions -> trash page).
        out = attention(q, k, v, spec, qcfg, q_offset=0)
        new_cache = _paged_write_prefill(cache, k, v, positions, lengths,
                                         page_table, mode, qcfg)
    elif cache is not None and decode:
        # Ring-buffer cache: slot(p) = p % span (full caches are span>=pos+1).
        pos = positions[0, 0]
        span = cache["k"].shape[2]
        slot = pos % span
        kv4 = mode == "int" and qcfg.kv_bits == 4
        if kv4:
            from repro.core.quant import pack_int4, qrange
            qmin, qmax = qrange(4)
            kq = pack_int4(jnp.squeeze(jnp.clip(
                jnp.round(k / cache["k_scale"]), qmin, qmax
            ).astype(jnp.int8), 2))
            vq = pack_int4(jnp.squeeze(jnp.clip(
                jnp.round(v / cache["v_scale"]), qmin, qmax
            ).astype(jnp.int8), 2))
        elif mode == "int":
            kq = jnp.squeeze(
                jnp.round(k / cache["k_scale"]).astype(jnp.int8), 2)
            vq = jnp.squeeze(
                jnp.round(v / cache["v_scale"]).astype(jnp.int8), 2)
        else:
            kq, vq = jnp.squeeze(k, 2), jnp.squeeze(v, 2)
        ck = jax.lax.dynamic_update_index_in_dim(cache["k"], kq, slot, 2)
        cv = jax.lax.dynamic_update_index_in_dim(cache["v"], vq, slot, 2)
        new_cache = dict(cache, k=ck, v=cv)
        if kv4:
            # Packed nibbles go to attention as stored (uint8 marks the
            # packing); the decode kernel reads them in place and the XLA
            # fallback unpacks to int8 codes — never a float copy.
            k_all = QTensor(ck, cache["k_scale"], 4)
            v_all = QTensor(cv, cache["v_scale"], 4)
        elif mode == "int":
            k_all = QTensor(ck, cache["k_scale"], qcfg.kv_bits)
            v_all = QTensor(cv, cache["v_scale"], qcfg.kv_bits)
        else:
            k_all, v_all = ck, cv
        # Position of ring slot j: pos - ((slot - j) mod span); < 0 = unwritten.
        j = jnp.arange(span)
        k_positions = pos - jnp.mod(slot - j, span)
        out = attention(q, k_all, v_all, spec, qcfg, q_offset=pos,
                        k_positions=k_positions)
    else:
        # Non-decode positions are always arange(s): a STATIC zero offset
        # (traced offsets would veto the Pallas fused-attention dispatch).
        out = attention(q, k, v, spec, qcfg,
                        q_offset=positions[0, 0] if decode else 0)
        if cache is not None:                     # prefill: write cache
            span = cache["k"].shape[2]
            s_in = k.shape[2]
            if s_in >= span:
                # Place position p at ring slot p % span.
                shift = (s_in - span) % span
                ks_ = jnp.roll(k[:, :, -span:], shift, axis=2)
                vs_ = jnp.roll(v[:, :, -span:], shift, axis=2)
            else:
                pad = [(0, 0), (0, 0), (0, span - s_in), (0, 0)]
                ks_, vs_ = jnp.pad(k, pad), jnp.pad(v, pad)
            if mode == "int" and qcfg.kv_bits == 4:
                from repro.core.quant import pack_int4
                ksc = jnp.max(jnp.abs(ks_)).astype(jnp.float32) / 7.
                vsc = jnp.max(jnp.abs(vs_)).astype(jnp.float32) / 7.
                kq4 = jnp.clip(jnp.round(ks_ / ksc), -8, 7).astype(jnp.int8)
                vq4 = jnp.clip(jnp.round(vs_ / vsc), -8, 7).astype(jnp.int8)
                new_cache = dict(cache, k=pack_int4(kq4), v=pack_int4(vq4),
                                 k_scale=ksc, v_scale=vsc)
            elif mode == "int":
                ksc = jnp.max(jnp.abs(ks_)).astype(jnp.float32) / 127.
                vsc = jnp.max(jnp.abs(vs_)).astype(jnp.float32) / 127.
                new_cache = dict(cache,
                                 k=jnp.round(ks_ / ksc).astype(jnp.int8),
                                 v=jnp.round(vs_ / vsc).astype(jnp.int8),
                                 k_scale=ksc, v_scale=vsc)
            else:
                new_cache = dict(cache, k=ks_.astype(cache["k"].dtype),
                                 v=vs_.astype(cache["v"].dtype))

    out = _merge(out)
    return dense(out, p["wo"], qcfg, tp="row"), new_cache


def _merge(x):
    b, h, s, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, h * d)


def apply_block(x, p, cfg: LMConfig, kind: str, *, positions, cache=None,
                decode=False, page_table=None, lengths=None,
                prefix_len: int = 0):
    aux = {}
    h = apply_norm(x, p["ln1"], cfg.norm)
    h = shard(h, "batch", "seq_tp", None)
    if kind in ("attn", "local"):
        out, new_cache = _attn_mixer(h, p["attn"], cfg, kind, positions,
                                     cache, decode, page_table, lengths,
                                     prefix_len)
    elif kind == "rglru":
        out, new_cache = rglru_block(h, p["rglru"], cfg.quant,
                                     state=cache if decode else None)
    elif kind == "ssd":
        out, new_cache = ssd_block(h, p["ssd"], cfg.ssd, cfg.quant,
                                   state=cache if decode else None)
    else:
        raise ValueError(kind)
    x = x + out.astype(x.dtype)
    if cfg.d_ff > 0:
        h2 = apply_norm(x, p["ln2"], cfg.norm)
        if cfg.moe is not None:
            b, s, d = h2.shape
            out2, aux = moe_lib.moe_ffn(h2.reshape(b * s, d), p["ffn"],
                                        cfg.moe, cfg.quant, act=cfg.act)
            out2 = out2.reshape(b, s, d)
        else:
            out2 = mlp(h2, p["ffn"], cfg.quant, act=cfg.act)
        x = x + out2.astype(x.dtype)
    x = shard(x, "batch", "seq_tp", None)
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# Stack
# ---------------------------------------------------------------------------

def _zeros_aux():
    return jnp.zeros((), jnp.float32)


def stack_forward(x, params, cfg: LMConfig, *, positions, cache=None,
                  decode=False, page_table=None, lengths=None,
                  prefix_len: int = 0):
    unit, n_units, rem = unit_structure(cfg)
    has_cache = cache is not None
    aux = _zeros_aux()

    # page_table/lengths are shared (not layer-stacked): they ride into the
    # scanned unit body as closure constants, not scanned xs.
    def unit_body(carry, xs):
        x, aux = carry
        up = xs[0]
        uc = xs[1] if has_cache else None
        new_uc = {}
        for j, kind in enumerate(unit):
            bc = uc[f"b{j}"] if has_cache else None
            x, nbc, a = apply_block(x, up[f"b{j}"], cfg, kind,
                                    positions=positions, cache=bc,
                                    decode=decode, page_table=page_table,
                                    lengths=lengths, prefix_len=prefix_len)
            new_uc[f"b{j}"] = nbc
            if "lb_loss" in a:
                aux = aux + a["lb_loss"]
        return (x, aux), (new_uc if has_cache else None)

    body = unit_body
    if cfg.remat and not decode:
        body = jax.checkpoint(
            unit_body,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)

    new_cache = dict(cache) if has_cache else None
    if n_units:
        xs = (params["units"], cache["units"]) if has_cache \
            else (params["units"],)
        (x, aux), unit_caches = _scan(body, (x, aux), xs)
        if has_cache:
            new_cache["units"] = unit_caches
    for i, kind in enumerate(rem):
        bc = cache[f"rem{i}"] if has_cache else None
        x, nbc, a = apply_block(x, params[f"rem{i}"], cfg, kind,
                                positions=positions, cache=bc, decode=decode,
                                page_table=page_table, lengths=lengths,
                                prefix_len=prefix_len)
        if has_cache:
            new_cache[f"rem{i}"] = nbc
        if "lb_loss" in a:
            aux = aux + a["lb_loss"]
    return x, new_cache, aux


def _inputs_to_x(params, batch, cfg: LMConfig):
    x = embed_lookup(batch["tokens"], params["embed"], cfg.jdtype)
    if cfg.frontend == "patch" and "patches" in batch:
        x = jnp.concatenate([batch["patches"].astype(cfg.jdtype), x], axis=1)
    return shard(x, "batch", None, None)


def forward(params, batch, cfg: LMConfig, *, cache=None, decode=False,
            prefix_len: int = 0):
    """Returns (pre-head hidden states, new_cache, aux).

    With a paged cache, ``cache["pos"]`` is per-sequence (B,) — each row
    decodes at its own position; inactive rows (``pos < 0``) stay frozen.
    Ragged prefill takes ``batch["lengths"]`` (defaults to the padded
    length) and leaves ``pos = lengths`` per row.

    ``prefix_len`` (static, paged prefill only): ``batch["tokens"]`` is
    the TAIL of a prompt whose first ``prefix_len`` positions are already
    cached in the rows' leading pages (prefix sharing) — positions start
    at ``prefix_len``, attention runs the tail-over-cached-prefix path,
    and ``pos`` lands at ``prefix_len + lengths``.
    """
    x = _inputs_to_x(params, batch, cfg)
    paged = cache is not None and "page_table" in cache
    page_table = cache["page_table"] if paged else None
    lengths = batch.get("lengths") if paged and not decode else None
    if decode:
        positions = cache["pos"][:, None] if paged else \
            jnp.broadcast_to(cache["pos"], (x.shape[0], 1))
    else:
        positions = jnp.broadcast_to(prefix_len + jnp.arange(x.shape[1]),
                                     (x.shape[0], x.shape[1]))
    x, new_cache, aux = stack_forward(x, params, cfg, positions=positions,
                                      cache=cache, decode=decode,
                                      page_table=page_table, lengths=lengths,
                                      prefix_len=0 if decode else prefix_len)
    x = apply_norm(x, params["final_norm"], cfg.norm)
    if new_cache is not None:
        if paged:
            if decode:           # inactive rows (pos < 0) do not advance
                new_cache["pos"] = jnp.where(cache["pos"] >= 0,
                                             cache["pos"] + 1, cache["pos"])
            else:
                new_cache["pos"] = prefix_len + (jnp.full(
                    (x.shape[0],), x.shape[1], jnp.int32)
                    if lengths is None else
                    jnp.asarray(lengths, jnp.int32))
        else:
            new_cache["pos"] = (cache["pos"] if cache else 0) + \
                (1 if decode else x.shape[1])
    return x, new_cache, aux


def logits_fn(params, x, cfg: LMConfig):
    return dense(x, params["lm_head"], cfg.quant)


def lm_loss(params, batch, cfg: LMConfig):
    """Causal LM loss, sequence-chunked so (B,S,V) logits never materialize."""
    x, _, aux = forward(params, batch, cfg)
    labels = batch["labels"]
    if cfg.frontend == "patch":                   # frontend tokens: no loss
        x = x[:, -labels.shape[1]:]
    b, s, d = x.shape
    c = next(cc for cc in range(min(cfg.loss_chunk, s), 0, -1) if s % cc == 0)
    xc = jnp.moveaxis(x.reshape(b, s // c, c, d), 1, 0)
    lc = jnp.moveaxis(labels.reshape(b, s // c, c), 1, 0)

    def chunk(tot, xs):
        xch, lch = xs
        logits = logits_fn(params, xch, cfg).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lch[..., None], axis=-1)[..., 0]
        return tot + jnp.sum(logz - gold), None

    tot, _ = _scan(chunk, jnp.zeros((), jnp.float32), (xc, lc))
    loss = tot / (b * s)
    return loss + 0.01 * aux, {"nll": loss, "aux": aux}


def prefill(params, batch, cfg: LMConfig, max_len: Optional[int] = None):
    """Run the full prompt, produce cache + last-position logits."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    cache = init_cache(cfg, b, max_len or s)
    x, cache, _ = forward(params, batch, cfg, cache=cache, decode=False)
    logits = logits_fn(params, x[:, -1:], cfg)
    return logits, cache


def paged_prefill(params, batch, cfg: LMConfig, cache, *,
                  prefix_len: int = 0):
    """Ragged prompt prefill into an existing paged cache.

    ``batch["tokens"]`` is (B, S) right-padded; ``batch["lengths"]`` (B,)
    gives each row's true prompt length (default S).  Pages named by
    ``cache["page_table"]`` must already be allocated for every row's
    prompt (see :mod:`repro.launch.engine`); pad positions write to the
    trash page.  With ``prefix_len`` (static), tokens are the TAIL of a
    prompt whose first ``prefix_len`` positions are already cached in the
    rows' leading ``ceil(prefix_len / page_size)`` pages (prefix sharing).
    Returns (last-real-position logits (B, 1, V), cache).
    """
    x, cache, _ = forward(params, batch, cfg, cache=cache, decode=False,
                          prefix_len=prefix_len)
    lengths = batch.get("lengths")
    if lengths is None:
        last = x[:, -1:]
    else:
        idx = jnp.clip(jnp.asarray(lengths, jnp.int32) - 1, 0, x.shape[1] - 1)
        last = jnp.take_along_axis(x, idx[:, None, None], axis=1)
    return logits_fn(params, last, cfg), cache


# Cache leaves indexed by PHYSICAL page id (shared across sequences), as
# opposed to per-row leaves: the one list the admission view, the row
# installer and the engine's copy-on-write page copy all special-case.
POOL_KEYS = ("k_pages", "v_pages", "page_k_scale", "page_v_scale")


def copy_page(cache, src: int, dst: int):
    """Duplicate physical page ``src`` into ``dst`` across every pool leaf
    (codes AND per-page scales, every attention layer) — the device half of
    the engine's copy-on-write: the copied page keeps the grid it was
    prefilled with, and the source page is never written again by the new
    owner.  ``units`` subtrees carry a leading layer-stack axis."""
    def walk(c, stacked):
        out = {}
        for key, leaf in c.items():
            if isinstance(leaf, dict):
                out[key] = walk(leaf, stacked or key == "units")
            elif key in POOL_KEYS:
                out[key] = leaf.at[:, dst].set(leaf[:, src]) if stacked \
                    else leaf.at[dst].set(leaf[src])
            else:
                out[key] = leaf
        return out

    return walk(cache, False)


def page_scale_pools(cache):
    """Yield ``(path, page_k_scale, page_v_scale)`` for every attention
    layer's per-physical-page scale pools in a paged cache.

    The engine's invariant auditor (``PagedEngine.audit``) walks these to
    assert every page's quantization grid stays finite and positive —
    decode writes (``_paged_write_decode``) quantize onto
    ``page_k_scale[phys]``, so one corrupted scale silently poisons every
    later token written to that page.  ``units`` subtree leaves carry a
    leading layer-stack axis; the pools are yielded as stored (trash page
    included — callers decide whether to exempt it)."""
    def walk(c, path):
        if "page_k_scale" in c:
            yield path, c["page_k_scale"], c["page_v_scale"]
        for key, leaf in c.items():
            if isinstance(leaf, dict):
                yield from walk(leaf, f"{path}/{key}" if path else key)

    yield from walk(cache, "")


def _admission_view(cache, w: int, page_table):
    """W-row prefill view over a B-row paged cache.

    Page pools are SHARED (the view's writes land directly in the serving
    cache's pools through ``page_table``); every per-row leaf (scales,
    recurrent states, pos) is fresh — prefill overwrites them all before
    anything reads them, so zeros suffice.  ``units`` subtrees carry a
    leading layer-stack axis.
    """
    def walk(c, stacked):
        out = {}
        for key, leaf in c.items():
            if isinstance(leaf, dict):
                out[key] = walk(leaf, stacked or key == "units")
            elif key in POOL_KEYS:
                out[key] = leaf                    # pool-indexed: shared
            elif stacked:
                out[key] = jnp.zeros((leaf.shape[0], w) + leaf.shape[2:],
                                     leaf.dtype)
            else:
                out[key] = jnp.zeros((w,) + leaf.shape[1:], leaf.dtype)
        return out

    view = walk({k: v for k, v in cache.items()
                 if k not in ("pos", "page_table")}, False)
    view["pos"] = jnp.zeros((w,), jnp.int32)
    view["page_table"] = jnp.asarray(page_table, jnp.int32)
    return view


def _install_rows(cache, view, rows):
    """Scatter a W-row admission view into the B-row cache at ``rows``.

    Pools replace wholesale (the view's prefill wrote only the admissions'
    reserved pages plus the trash page, so running tenants' pages are
    untouched); per-row leaves land in their target rows.  Host-owned
    ``pos``/``page_table`` keep the big cache's values — the engine owns
    and pushes them.
    """
    def walk(big, small, stacked):
        out = {}
        for key, bleaf in big.items():
            if isinstance(bleaf, dict):
                out[key] = walk(bleaf, small[key], stacked or key == "units")
            elif key in POOL_KEYS:
                out[key] = small[key]              # pool-indexed: wholesale
            elif stacked:
                out[key] = bleaf.at[:, rows].set(small[key])
            else:
                out[key] = bleaf.at[rows].set(small[key])
        return out

    host = {k: cache[k] for k in ("pos", "page_table")}
    out = walk({k: v for k, v in cache.items() if k not in host},
               {k: v for k, v in view.items() if k not in host}, False)
    out.update(host)
    return out


def admission_prefill(params, batch, cfg: LMConfig, cache, rows, page_table,
                      *, prefix_len: int = 0):
    """Batched ragged admission prefill straight into the shared page pools.

    ``batch["tokens"]`` (W, S) right-padded to one bucket with
    ``batch["lengths"]`` (W,); ``page_table`` (W, max_pages) holds each
    admission's RESERVED physical page ids in ``cache``'s pools; ``rows``
    (W,) int32 names the decode-batch rows the admissions occupy.  KV codes
    are written through the page tables directly into the shared pools (pad
    positions to the trash page) and per-row leaves (per-sequence scales,
    recurrent states) land at ``rows`` — no private prefill cache and no
    page-copy pass.  Per-sequence activation grids (core.api / dispatch /
    layers.attention) make every row bit-identical to a solo prefill of the
    same prompt at the same bucket, so a burst of W admissions costs ONE
    forward instead of W without changing a single served token.

    ``prefix_len`` (static): the admissions' tokens are prompt TAILS whose
    first ``prefix_len`` positions are already cached — each row's leading
    logical pages map onto existing physical pages (shared, refcounted by
    the engine), the tail attends them through their stored codes and
    per-page scales, and only the tail's own pages are written.  A chunk-1
    prefix prefill is itself just this call with ``prefix_len=0`` over the
    prefix tokens, which is what makes a shared prefix bit-identical to a
    privately prefilled one.  Returns (last-real-position logits (W, 1, V),
    updated cache).

    The same mechanics make prefill RESUMABLE in fixed-token chunks (the
    engine's chunked-prefill scheduler): chunk i+1 is this call with
    ``prefix_len`` = chunk i's end offset, attending everything already
    written through its stored codes and per-physical-page scale grids.
    With page-aligned chunk boundaries each physical page's scale grid is
    registered by exactly one chunk (the one containing its first token),
    so the stored codes — and every token decoded from them — are a pure
    function of the cut plan, independent of launch step or batching
    width.
    """
    w = batch["tokens"].shape[0]
    view = _admission_view(cache, w, page_table)
    logits, view = paged_prefill(params, batch, cfg, view,
                                 prefix_len=prefix_len)
    return logits, _install_rows(cache, view, jnp.asarray(rows, jnp.int32))


def decode_step(params, token, cache, cfg: LMConfig):
    """One serving step: token (B, 1) + cache -> logits (B, 1, V) + cache."""
    x, cache, _ = forward(params, {"tokens": token}, cfg, cache=cache,
                          decode=True)
    return logits_fn(params, x, cfg), cache
