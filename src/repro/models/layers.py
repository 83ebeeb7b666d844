"""Core neural-net layers, pure-functional JAX.

Convention: every module is an ``init_*(key, ...) -> params`` plus an
``apply`` function taking ``(params, x, ...)``.  Params are plain dicts so the
ASA sharding layer can mirror them with PartitionSpec trees (see
``core/sharding.py`` — spec builders are written alongside these inits and a
property test asserts tree-structure equality).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

Params = dict
Array = jax.Array


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def _normal(key, shape, dtype, stddev):
    return (stddev * jax.random.truncated_normal(key, -2.0, 2.0, shape)).astype(dtype)


def init_dense(key, d_in: int, d_out: int, *, bias: bool = False,
               dtype=jnp.float32, scale: Optional[float] = None) -> Params:
    stddev = scale if scale is not None else 1.0 / math.sqrt(d_in)
    p = {"w": _normal(key, (d_in, d_out), dtype, stddev)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense(p: Params, x: Array) -> Array:
    y = x @ p["w"].astype(x.dtype)
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    return y


def init_rmsnorm(d: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(p: Params, x: Array, eps: float = 1e-6) -> Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)
    return y.astype(dt)


def init_layernorm(d: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm(p: Params, x: Array, eps: float = 1e-5) -> Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return y.astype(dt)


# ---------------------------------------------------------------------------
# rotary position embedding
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float = 10000.0) -> Array:
    """Inverse frequencies, shape (head_dim // 2,). float32."""
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponents)


def apply_rope(x: Array, positions: Array, theta: float = 10000.0) -> Array:
    """x: (..., seq, heads, head_dim); positions: broadcastable to (..., seq)."""
    dt = x.dtype
    hd = x.shape[-1]
    inv = rope_frequencies(hd, theta)
    angles = positions[..., :, None].astype(jnp.float32) * inv  # (..., S, hd/2)
    angles = angles[..., None, :]                               # (..., S, 1, hd/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(dt)


# ---------------------------------------------------------------------------
# attention (MHA / GQA / MQA, optional qk-norm, causal or bidirectional,
# optional cross-attention, optional output gate)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    rope_theta: float = 10000.0
    use_rope: bool = True
    qk_norm: bool = False
    causal: bool = True
    bias: bool = False
    gated: bool = False          # tanh-gated output (llama-vision cross blocks)
    softmax_scale: Optional[float] = None

    @property
    def q_dim(self):
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self):
        return self.n_kv_heads * self.head_dim


def init_attention(key, cfg: AttnConfig, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 5)
    p = {
        "wq": init_dense(ks[0], cfg.d_model, cfg.q_dim, bias=cfg.bias, dtype=dtype),
        "wk": init_dense(ks[1], cfg.d_model, cfg.kv_dim, bias=cfg.bias, dtype=dtype),
        "wv": init_dense(ks[2], cfg.d_model, cfg.kv_dim, bias=cfg.bias, dtype=dtype),
        "wo": init_dense(ks[3], cfg.q_dim, cfg.d_model, bias=cfg.bias, dtype=dtype,
                         scale=1.0 / math.sqrt(cfg.q_dim)),
    }
    if cfg.qk_norm:
        p["q_norm"] = init_rmsnorm(cfg.head_dim, dtype)
        p["k_norm"] = init_rmsnorm(cfg.head_dim, dtype)
    if cfg.gated:
        p["gate"] = jnp.zeros((), dtype)
    return p


SDPA_CHUNK = 512          # q-block size for the chunked XLA path
SDPA_CHUNK_THRESHOLD = 1024   # chunk when S*T exceeds threshold^2


def _sdpa_dense(q, k, v, *, causal, scale):
    """q (B,S,Hkv,G,D) against k/v (B,T,Hkv,D) -> (B,S,Hkv,G,D)."""
    S, T = q.shape[1], k.shape[1]
    logits = jnp.einsum("bskgd,btkd->bkgst", q, k).astype(jnp.float32) * scale
    if causal:
        mask = jnp.arange(S)[:, None] >= jnp.arange(T)[None, :]   # (S, T)
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bkgst,btkd->bskgd", probs, v)


def _sdpa_chunked(q, k, v, *, causal, scale, chunk=SDPA_CHUNK):
    """Scan over query blocks: peak logits memory B*H*chunk*T instead of
    B*H*S*T.  XLA lowers the scan body once; this is the memory-sane lowering
    the dry-run uses for 4k-32k sequences (the Pallas kernel replaces it on
    real TPUs).  Shapes as :func:`_sdpa_dense`."""
    B, S, Hkv, G, D = q.shape
    T = k.shape[1]
    C = min(chunk, S)
    pad = (-S) % C
    qp = jnp.arange(S)
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
        qp = jnp.pad(qp, (0, pad), constant_values=-1)   # -1 => fully masked
    nq = q.shape[1] // C
    q_blocks = jnp.moveaxis(q.reshape(B, nq, C, Hkv, G, D), 1, 0)
    p_blocks = qp.reshape(nq, C)
    kp = jnp.arange(T)

    def body(_, xs):
        qb, pb = xs                                  # (B,C,Hkv,G,D), (C,)
        lg = jnp.einsum("bckgd,btkd->bkgct", qb, k).astype(jnp.float32) * scale
        if causal:
            mask = pb[:, None] >= kp[None, :]
        else:
            mask = (pb[:, None] >= 0) & jnp.ones((1, T), bool)
        lg = jnp.where(mask, lg, -1e30)
        pr = jax.nn.softmax(lg, axis=-1).astype(v.dtype)
        ob = jnp.einsum("bkgct,btkd->bckgd", pr, v)
        return 0.0, ob

    _, out = jax.lax.scan(body, 0.0, (q_blocks, p_blocks))
    out = jnp.moveaxis(out, 0, 1).reshape(B, nq * C, Hkv, G, D)
    return out[:, :S]


def _sdpa(q: Array, k: Array, v: Array, *, causal: bool,
          scale: float) -> Array:
    """q: (B,S,H,D); k,v: (B,T,Hkv,D) with Hkv | H.  Pure-jnp reference path
    (the 'xla' impl); auto-switches to the q-block-chunked form when the
    logits tensor would be large.  As in :func:`_paged_sdpa`, each kv head
    is contracted with its own group of G = H/Hkv query heads, so K/V are
    never copied to H heads."""
    B, S, H, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    q = q.reshape(B, S, Hkv, H // Hkv, D)      # kv head k meets q group k
    k, v = k.astype(q.dtype), v.astype(q.dtype)
    if S * T > SDPA_CHUNK_THRESHOLD ** 2 and S > SDPA_CHUNK:
        out = _sdpa_chunked(q, k, v, causal=causal, scale=scale)
    else:
        out = _sdpa_dense(q, k, v, causal=causal, scale=scale)
    return out.reshape(B, S, H, D)


def _paged_sdpa(q: Array, k: Array, v: Array, *, scale: float,
                q_pos: Array, kv_len: Array) -> Array:
    """SDPA with *per-sequence* causal masks: q (B,S,H,D), k/v (B,T,Hkv,D)
    with Hkv | H, q_pos (B,S), kv_len (B,).

    Each kv head is contracted with its own group of G = H/Hkv query heads
    (G = 1 is MHA), so the gathered K/V is read once at Hkv heads and never
    copied to H; the products and their sums over D are those of the
    broadcast form.  Masked entries contribute exactly-zero probability (exp
    underflows), so the rows of the table past a sequence's length and the
    padded rows of a chunk change nothing: the result is attention over the
    sequence's own prefix.  The greedy-parity test in tests/test_serving.py
    relies on this."""
    B, S, H, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    q = q.reshape(B, S, Hkv, H // Hkv, D)      # kv head k meets q group k
    logits = jnp.einsum("bskgd,btkd->bkgst", q, k).astype(jnp.float32) * scale
    kp = jnp.arange(T)
    mask = q_pos[:, :, None] >= kp[None, None, :]            # (B,S,T) causal
    mask = mask & (kp[None, None, :] < kv_len[:, None, None])
    logits = jnp.where(mask[:, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bkgst,btkd->bskgd", probs, v).reshape(B, S, H, D)


def paged_flat_indices(positions: Array, seq: int, block_tables: Array,
                       block_size: int,
                       new_lens: Optional[Array] = None
                       ) -> tuple[Array, Array]:
    """Logical->physical paging arithmetic shared by every paged cache
    (attention KV here, MLA latents in mla.py).

    Returns (q_pos (B, S), flat (B, S)): per-token absolute positions and
    flat row indices into an (NB * block_size, ...) pool for ``seq`` new
    tokens starting at positions[b].  Out-of-table writes (position beyond
    the table's capacity) and padded rows (>= new_lens[b]) divert to the
    null block's scratch rows — clamping them into a live block would
    silently overwrite resident state."""
    qp = positions[:, None] + jnp.arange(seq)[None, :]        # (B, S)
    logical = qp // block_size
    width = block_tables.shape[1]
    blk = jnp.take_along_axis(block_tables, jnp.minimum(logical, width - 1),
                              axis=1)
    flat = blk * block_size + qp % block_size                 # (B, S)
    flat = jnp.where(logical < width, flat, qp % block_size)
    if new_lens is not None:
        valid = jnp.arange(seq)[None, :] < new_lens[:, None]
        flat = jnp.where(valid, flat, jnp.arange(seq)[None, :] % block_size)
    return qp, flat


def layer_index(layer: Optional[Array], *rows: Array) -> tuple:
    """Index tuple for ``rows`` of a pool, or, with ``layer``, of that layer
    of a stacked pool (L, ...): the layer index is broadcast beside the row
    indices, so that reading or writing the rows is one gather or one
    scatter and never a slice of the layer's whole pool."""
    if layer is None:
        return rows
    return (jnp.broadcast_to(layer, rows[0].shape),) + rows


def paged_write_gather(pool: Array, new: Array, flat: Array,
                       block_tables: Array,
                       layer: Optional[Array] = None) -> tuple[Array, Array]:
    """Write ``new`` (N, ...) at the flat row indices ``flat`` (N,) (block *
    block_size + offset, from :func:`paged_flat_indices`) of a block pool,
    then gather each sequence's pages back in logical order: -> (pool,
    (B, max_blocks * block_size, ...)).

    ``pool`` is one layer's (NB, BS, ...) pool, or with ``layer`` the
    stacked (L, NB, BS, ...) pools that transformer._apply_segment carries
    through its layer scan: the write is then one scatter at (layer, block,
    offset) and the read one gather at (layer, block), so XLA updates the
    carry in place and no layer's pool is copied out of the stack."""
    BS = pool.shape[1 if layer is None else 2]
    blk, off = jnp.divmod(flat, BS)
    pool = pool.at[layer_index(layer, blk, off)].set(new.astype(pool.dtype))
    view = pool[layer_index(layer, block_tables)]      # (B, MB, BS, ...)
    B, MB = block_tables.shape
    return pool, view.reshape((B, MB * BS) + view.shape[3:])


def paged_attention(p: Params, cfg: AttnConfig, x: Array, *,
                    cache: Params, positions: Array,
                    block_tables: Array,
                    new_lens: Optional[Array] = None,
                    layer: Optional[Array] = None) -> tuple[Array, Params]:
    """Self-attention over a block-paged KV pool (vLLM-style paged KV).

    cache: {"k": (NB, BS, Hkv, D), "v": ...} — a *physical block pool* shared
    by every request; ``block_tables`` (B, max_blocks) int32 maps each
    sequence's logical block j to a physical block (block 0 is the reserved
    null block — idle batch slots point every entry there).  With ``layer``
    the pools are the stacked (L, NB, BS, Hkv, D) scan carry of
    transformer._apply_segment and this call is layer ``layer``: the new
    rows are written into the stack in place and the block tables gathered
    from it (:func:`paged_write_gather`).  ``positions``
    (B,) int32 is each sequence's token count before this call; the S new
    tokens are written at logical positions positions[b]..positions[b]+S-1
    and attention runs over the gathered logical view with per-sequence
    causal/length masks.  ``new_lens`` (B,) < S marks rows past it as
    padding: their writes are diverted to the null block and their tokens
    never enter kv_len, so callers can fix the chunk shape (one jit trace)
    regardless of actual prompt-chunk length.  Serving layer:
    repro/serving/paged_cache.py.
    """
    B, S, _ = x.shape
    q = dense(p["wq"], x).reshape(B, S, cfg.n_heads, cfg.head_dim)
    k = dense(p["wk"], x).reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    v = dense(p["wv"], x).reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q)
        k = rmsnorm(p["k_norm"], k)
    # scatter new k/v into their pages (flat row index = block * BS + offset;
    # overrun/padded writes divert to the null block — see paged_flat_indices)
    BS = cache["k"].shape[-3]
    qp, flat = paged_flat_indices(positions, S, block_tables, BS,
                                  new_lens=new_lens)
    if cfg.use_rope:
        q = apply_rope(q, qp, cfg.rope_theta)
        k = apply_rope(k, qp, cfg.rope_theta)
    flat = flat.reshape(-1)                                  # (B*S,)
    rows = (B * S, cfg.n_kv_heads, cfg.head_dim)
    ck, gk = paged_write_gather(cache["k"], k.reshape(rows), flat,
                                block_tables, layer)
    cv, gv = paged_write_gather(cache["v"], v.reshape(rows), flat,
                                block_tables, layer)
    scale = cfg.softmax_scale or (1.0 / math.sqrt(cfg.head_dim))
    kv_len = positions + (new_lens if new_lens is not None else S)
    out = _paged_sdpa(q, gk.astype(q.dtype), gv.astype(q.dtype), scale=scale,
                      q_pos=qp, kv_len=kv_len)
    y = dense(p["wo"], out.reshape(B, S, cfg.q_dim))
    return y, {"k": ck, "v": cv}


def attention(p: Params, cfg: AttnConfig, x: Array, *,
              kv_input: Optional[Array] = None,
              cache: Optional[Params] = None,
              positions: Optional[Array] = None,
              block_tables: Optional[Array] = None,
              new_lens: Optional[Array] = None,
              layer: Optional[Array] = None,
              impl: str = "xla") -> tuple[Array, Optional[Params]]:
    """Self- or cross-attention.

    With ``block_tables`` the cache is a paged block pool and the call
    dispatches to :func:`paged_attention` (per-sequence positions; with
    ``layer``, layer ``layer`` of the stacked pools), which returns the
    updated pool.  Otherwise a ``cache`` holds read-only cross K/V rows
    {"k": (B,T,Hkv,D), "v": ...} (the slot-state pools' rows), ``kv_input``
    is the source of cross K/V, and with neither this is training or
    encoder self-attention; these return no cache.
    """
    if block_tables is not None:
        assert cache is not None and positions is not None
        return paged_attention(p, cfg, x, cache=cache, positions=positions,
                               block_tables=block_tables, new_lens=new_lens,
                               layer=layer)
    B, S, _ = x.shape
    q = dense(p["wq"], x).reshape(B, S, cfg.n_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q)
    scale = cfg.softmax_scale or (1.0 / math.sqrt(cfg.head_dim))

    if kv_input is not None or cache is not None:       # cross-attention
        if kv_input is not None:
            T = kv_input.shape[1]
            k = dense(p["wk"], kv_input).reshape(B, T, cfg.n_kv_heads, cfg.head_dim)
            v = dense(p["wv"], kv_input).reshape(B, T, cfg.n_kv_heads, cfg.head_dim)
            if cfg.qk_norm:
                k = rmsnorm(p["k_norm"], k)
        else:
            k, v = cache["k"], cache["v"]
        if cfg.use_rope:
            q = apply_rope(q, positions if positions is not None else jnp.arange(S),
                           cfg.rope_theta)
        out = _sdpa(q, k, v, causal=False, scale=scale)
    else:
        k = dense(p["wk"], x).reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
        v = dense(p["wv"], x).reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
        if cfg.qk_norm:
            k = rmsnorm(p["k_norm"], k)
        if positions is None:
            positions = jnp.arange(S)
        if cfg.use_rope:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
        if impl == "pallas" and cfg.causal:
            from repro.kernels import ops as kops
            out = kops.flash_attention(q, k, v, scale=scale)
        else:
            out = _sdpa(q, k, v, causal=cfg.causal, scale=scale)
    y = dense(p["wo"], out.reshape(B, S, cfg.q_dim))
    if cfg.gated:
        y = jnp.tanh(p["gate"].astype(y.dtype)) * y
    return y, None


def init_paged_attention_cache(cfg: AttnConfig, num_blocks: int,
                               block_size: int, dtype=jnp.bfloat16) -> Params:
    """Physical KV block pool shared by all requests (no batch axis; block 0
    is the reserved null block).  See :func:`paged_attention`."""
    shp = (num_blocks, block_size, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shp, dtype), "v": jnp.zeros(shp, dtype)}


# ---------------------------------------------------------------------------
# MLPs: SwiGLU / GeGLU / plain GELU
# ---------------------------------------------------------------------------

def init_mlp(key, d_model: int, d_ff: int, *, act: str = "silu",
             bias: bool = False, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 3)
    p = {"w_in": init_dense(ks[0], d_model, d_ff, bias=bias, dtype=dtype),
         "w_out": init_dense(ks[2], d_ff, d_model, bias=bias, dtype=dtype,
                             scale=1.0 / math.sqrt(d_ff))}
    if act in ("silu", "geglu"):  # gated variants carry a second in-proj
        p["w_gate"] = init_dense(ks[1], d_model, d_ff, bias=bias, dtype=dtype)
    return p


def mlp(p: Params, x: Array, act: str = "silu") -> Array:
    h = dense(p["w_in"], x)
    if act == "silu":
        h = jax.nn.silu(dense(p["w_gate"], x)) * h
    elif act == "geglu":
        h = jax.nn.gelu(dense(p["w_gate"], x), approximate=True) * h
    elif act == "gelu":
        h = jax.nn.gelu(h, approximate=True)
    elif act == "relu":
        h = jax.nn.relu(h)
    else:
        raise ValueError(act)
    return dense(p["w_out"], h)


# ---------------------------------------------------------------------------
# embeddings & head
# ---------------------------------------------------------------------------

def init_embedding(key, vocab: int, d_model: int, dtype=jnp.float32) -> Params:
    return {"embedding": _normal(key, (vocab, d_model), dtype, 1.0)}


def embed(p: Params, tokens: Array, d_model: int) -> Array:
    return jnp.take(p["embedding"], tokens, axis=0) * (d_model ** 0.5)


def unembed(p: Params, x: Array) -> Array:
    """Tied head: logits = x @ E^T (fp32 accumulation)."""
    return jnp.einsum("bsd,vd->bsv", x, p["embedding"]).astype(jnp.float32)
