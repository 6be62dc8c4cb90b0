"""Plain reference of the qwen2 RAG agent's decode: a Qwen2 decoder
(arXiv:2407.10671: GQA with QKV bias, rotate-half RoPE, RMSNorm, SwiGLU,
tied output head) at the configuration's precision, prefilling the
prompt with dense causal attention and then decoding each served token
with the paged KV cascade's selection written out in plain jnp:

  keys quantized to INT8 per (position, head), scale max|k| / 127;
  page centroids = the mean of each page's valid dequantized keys,
    re-quantized the same way;
  page prune: the query heads' INT8 codes (per head, scale max|q| / 127)
    take their top nibble (arithmetic shift by four) and score the
    centroids' top nibbles, times both scales, max over the group's
    heads; the top `npages` pages that hold a valid position are kept;
  sign prescreen: agreement of the query's and the keys' signs (a zero
    counts as positive), max over the group, the top `prescreen_c0` kept;
  approximate scan: the float query times the keys' top nibbles times
    their scales, max over the group, the top `top_k` kept;
  exact attention over those keys at INT8 times their scales, softmax
    scaled by head_dim ** -0.5, values in float32.

Ties go to the lower index, as `jax.lax.top_k` breaks them. Dense
attention is a different result: the cascade attends only the kept keys.

Precision: a float32 configuration runs in float32 at `highest` matmul
precision. A bfloat16 one keeps every activation in bfloat16 between
operations (a matmul's products exact, its sum in float32, then rounded;
norms, rotary, softmax and the cascade's scores in float32) and runs its
float32 matmuls at the platform's default precision, as plain jnp code
of that configuration does. The selection stages rank integer scores
full of ties, so a reference that rounds differently from the
configuration keeps other keys and reads gaps that are rounding, not
fault.

`init_params` makes the weights from the seed (the benchmark hands the
same weights, in bfloat16, to the program). The `fp8` mode is the
control: every projection and the output head take float8 (e4m3) inputs,
weights scaled per tensor and activations per row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


class Mode:
    """How the reference computes: `r` rounds an activation to the
    configuration's precision, `prec` is the matmul precision, and `fp8`
    (the control) feeds every projection and the output head float8."""

    def __init__(self, dtype: str, fp8: bool = False):
        self.fp8 = fp8
        dt = jnp.dtype(dtype)
        if dt == jnp.float32:
            self.prec = HIGHEST
            self.r = lambda x: x
        else:
            self.prec = None
            self.r = lambda x: x.astype(dt).astype(jnp.float32)


def seed_key(seed: int):
    key = jax.random.PRNGKey(int(seed) & 0xFFFFFFFF)
    return jax.random.fold_in(key, int(seed) >> 32)


def init_params(m: dict, key, dtype=jnp.bfloat16) -> dict:
    """Random weights from `key`, laid out as the program's dense model
    holds them (layers stacked on the leading axis)."""
    l, d = m["num_hidden_layers"], m["hidden_size"]
    h, kh, f = (m["num_attention_heads"], m["num_key_value_heads"],
                m["intermediate_size"])
    hd, v = d // h, m["vocab_size"]
    ks = iter(jax.random.split(key, 16))

    def w(shape, fan_in):
        return (jax.random.truncated_normal(next(ks), -2.0, 2.0, shape)
                * fan_in ** -0.5).astype(dtype)

    def around(shape, mean, std):
        return (mean + std * jax.random.normal(next(ks), shape)).astype(dtype)

    blocks = {
        "ln1": around((l, d), 1.0, 0.1),
        "wq": w((l, d, h * hd), d), "wk": w((l, d, kh * hd), d),
        "wv": w((l, d, kh * hd), d), "wo": w((l, h * hd, d), h * hd),
        "ln2": around((l, d), 1.0, 0.1),
        "w_gate": w((l, d, f), d), "w_up": w((l, d, f), d),
        "w_down": w((l, f, d), f),
        "bq": around((l, h * hd), 0.0, 0.02),
        "bk": around((l, kh * hd), 0.0, 0.02),
        "bv": around((l, kh * hd), 0.0, 0.02),
    }
    return {"embed": around((v, d), 0.0, 0.02), "blocks": blocks,
            "final_norm": around((d,), 1.0, 0.1)}


def make_params(m: dict, seed: int, dtype="bfloat16") -> dict:
    """The weights of `seed` in `dtype`, made on the device in one jitted
    call."""
    return jax.jit(functools.partial(init_params, m,
                                     dtype=jnp.dtype(dtype)))(seed_key(seed))


# ---------------------------------------------------------------------------
# Layers
# ---------------------------------------------------------------------------

def _fp8(x, axis):
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.maximum(amax, 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, md: Mode):
    if md.fp8:
        x, w = _fp8(x, -1), _fp8(w, None)
    return md.r(jnp.einsum("...d,de->...e", x, w, precision=md.prec))


def _rmsnorm(x, g, eps, md: Mode):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return md.r(md.r(x * jax.lax.rsqrt(var + eps)) * g)


def _rope(x, pos, theta, md: Mode):
    """x (..., S, H, hd), pos (S,) or (B, 1): rotate-half RoPE."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[..., None] * freqs
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return md.r(jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                                -1))


def _qkv(p, x, m, pos, md: Mode):
    h, kh = m["num_attention_heads"], m["num_key_value_heads"]
    hd = m["hidden_size"] // h
    r = md.r
    hn = _rmsnorm(x, p["ln1"], m["rms_norm_eps"], md)
    q = r(_mm(hn, p["wq"], md) + p["bq"]).reshape(*x.shape[:-1], h, hd)
    k = r(_mm(hn, p["wk"], md) + p["bk"]).reshape(*x.shape[:-1], kh, hd)
    v = r(_mm(hn, p["wv"], md) + p["bv"]).reshape(*x.shape[:-1], kh, hd)
    return (_rope(q, pos, m["rope_theta"], md),
            _rope(k, pos, m["rope_theta"], md), v)


def _mlp_out(p, x, o, m, md: Mode):
    r = md.r
    x = r(x + _mm(r(o), p["wo"], md))
    hn = _rmsnorm(x, p["ln2"], m["rms_norm_eps"], md)
    g = _mm(hn, p["w_gate"], md)
    u = _mm(hn, p["w_up"], md)
    return r(x + _mm(r(r(jax.nn.silu(g)) * u), p["w_down"], md))


def _logits(params, x, m, md: Mode):
    x = _rmsnorm(x, params["final_norm"], m["rms_norm_eps"], md)
    return _mm(x, params["embed"].T, md)


def quantize_rows(x):
    """INT8 per row of the last axis, scale max|x| / 127 (codes kept in
    [-127, 127]); returns (codes int32, scale)."""
    amax = jnp.max(jnp.abs(x), axis=-1)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    codes = jnp.clip(jnp.round(x / scale[..., None]), -127, 127)
    return codes.astype(jnp.int32), scale


def _nibble(codes):
    return jnp.right_shift(codes, 4)


# ---------------------------------------------------------------------------
# The KV cascade, one layer, one decode step
# ---------------------------------------------------------------------------

def kv_cascade(q, kc, ks, v, length, kv: dict, prec=HIGHEST):
    """q (B, H, hd) f32 against one layer's cache: key codes kc (B, T, KH,
    hd) int32, scales ks (B, T, KH), values v (B, T, KH, hd); length (B,)
    valid positions. Returns (B, H, hd)."""
    b, t, kh, hd = v.shape
    h = q.shape[1]
    g = h // kh
    pr, npages = kv["page_rows"], kv["npages"]
    p = t // pr
    qg = q.reshape(b, kh, g, hd)
    qc, qs = quantize_rows(qg)
    # page centroids
    kf = kc.astype(jnp.float32) * ks[..., None]
    pos = jnp.arange(t)
    live = pos[None, :] < length[:, None]                        # (B, T)
    pages = jnp.where(live[..., None, None], kf, 0.0).reshape(
        b, p, pr, kh, hd)
    cnt = jnp.sum(live.reshape(b, p, pr), axis=2).astype(jnp.float32)
    mean = jnp.sum(pages, axis=2) / jnp.maximum(cnt, 1.0)[..., None, None]
    cc, cs = quantize_rows(mean)                                 # (B,P,KH,hd)
    score = jnp.einsum("bkgd,bpkd->bkgp", _nibble(qc), _nibble(cc))
    key = (score.astype(jnp.float32) * qs[..., None]
           * cs.transpose(0, 2, 1)[:, :, None, :])
    key = jnp.max(key, axis=2)                                   # (B, KH, P)
    valid = (jnp.arange(p) * pr)[None, None, :] < length[:, None, None]
    key = jnp.where(valid, key, -jnp.inf)
    _, sel = jax.lax.top_k(key, min(npages, p))
    sel = jnp.sort(sel, axis=-1)
    rows = (sel[..., None] * pr + jnp.arange(pr)).reshape(b, kh, -1)
    member = rows < length[:, None, None]
    kc_l = kc.transpose(0, 2, 1, 3)                              # (B,KH,T,hd)
    ks_l = ks.transpose(0, 2, 1)
    v_l = v.transpose(0, 2, 1, 3)

    def gather(a, r):
        return jnp.take_along_axis(a, r[..., None] if a.ndim == 4 else r,
                                   axis=2)
    # sign prescreen
    c0 = min(kv["prescreen_c0"], rows.shape[-1])
    qsgn = jnp.where(qc < 0, -1, 1)
    ksgn = jnp.where(gather(kc_l, rows) < 0, -1, 1)
    s0 = jnp.max(jnp.einsum("bkgd,bkrd->bkgr", qsgn, ksgn), axis=2)
    s0 = jnp.where(member, s0, jnp.iinfo(jnp.int32).min)
    _, pick = jax.lax.top_k(s0, c0)
    pick = jnp.sort(pick, axis=-1)
    rows = jnp.take_along_axis(rows, pick, axis=-1)
    member = jnp.take_along_axis(member, pick, axis=-1)
    # approximate scan over the top nibbles
    kn = _nibble(gather(kc_l, rows)).astype(jnp.float32)
    s1 = jnp.einsum("bkgd,bkrd->bkgr", qg, kn, precision=prec)
    s1 = jnp.max(s1 * gather(ks_l, rows)[:, :, None, :], axis=2)
    s1 = jnp.where(member, s1, -1e30)
    _, pick = jax.lax.top_k(s1, min(kv["top_k"], rows.shape[-1]))
    rows = jnp.take_along_axis(rows, pick, axis=-1)
    member = jnp.take_along_axis(member, pick, axis=-1)
    # exact attention over the kept keys
    ksel = gather(kc_l, rows).astype(jnp.float32) \
        * gather(ks_l, rows)[..., None]
    vsel = gather(v_l, rows)
    s2 = jnp.einsum("bkgd,bkrd->bkgr", qg, ksel,
                    precision=prec) * hd ** -0.5
    mask = member[:, :, None, :]
    s2 = jnp.where(mask, s2, -1e30)
    e = jnp.where(mask, jnp.exp(s2 - jnp.max(s2, -1, keepdims=True)), 0.0)
    den = jnp.sum(e, -1, keepdims=True)
    pw = e / jnp.where(den > 0, den, 1.0)
    out = jnp.einsum("bkgr,bkrd->bkgd", pw, vsel, precision=prec)
    return out.reshape(b, h, hd)


# ---------------------------------------------------------------------------
# Teacher-forced logit gaps over a prompt and its served tokens
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("m_items", "kv_items",
                                             "fp8"))
def _run(params, prompt, served, probe, m_items, kv_items, fp8):
    m, kv = dict(m_items), dict(kv_items)
    md = Mode(m["torch_dtype"], fp8)
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    blocks = params["blocks"]
    b, s = prompt.shape
    n_new = served.shape[1]
    h, kh = m["num_attention_heads"], m["num_key_value_heads"]
    hd = m["hidden_size"] // h
    g = h // kh
    pr = kv["page_rows"]
    t = -(-(s + n_new) // pr) * pr

    # prefill: dense causal attention over the prompt
    x = params["embed"][prompt]
    pos = jnp.arange(s)

    def pre_layer(x, p):
        q, k, v = _qkv(p, x, m, pos, md)
        qg = q.reshape(b, s, kh, g, hd)
        sc = jnp.einsum("bskgd,btkd->bkgst", qg, k,
                        precision=md.prec) * hd ** -0.5
        causal = pos[:, None] >= pos[None, :]
        sc = jnp.where(causal, sc, -jnp.inf)
        o = jnp.einsum("bkgst,btkd->bskgd", jax.nn.softmax(sc, -1), v,
                       precision=md.prec).reshape(b, s, h * hd)
        return _mlp_out(p, x, o, m, md), (k, v)

    x, (ks_all, vs_all) = jax.lax.scan(pre_layer, x, blocks)
    first = _logits(params, x[:, -1], m, md)                    # (B, V)
    kc, ksc = quantize_rows(ks_all)                              # (L,B,S,KH,.)
    padn = t - s
    kc = jnp.pad(kc, ((0, 0), (0, 0), (0, padn), (0, 0), (0, 0)))
    ksc = jnp.pad(ksc, ((0, 0), (0, 0), (0, padn), (0, 0)))
    vc = jnp.pad(vs_all, ((0, 0), (0, 0), (0, padn), (0, 0), (0, 0)))

    def step(carry, i):
        kc, ksc, vc = carry
        tok = served[:, i]
        p_i = s + i
        length = jnp.full((b,), p_i + 1, jnp.int32)
        x = params["embed"][tok][:, None, :]                     # (B, 1, D)

        def layer(x, xs):
            p, kc_l, ks_l, vc_l = xs
            q, k, v = _qkv(p, x, m, jnp.full((b, 1), p_i), md)
            c, sc_ = quantize_rows(k[:, 0])
            kc_l = kc_l.at[:, p_i].set(c)
            ks_l = ks_l.at[:, p_i].set(sc_)
            vc_l = vc_l.at[:, p_i].set(v[:, 0])
            o = kv_cascade(q[:, 0], kc_l, ks_l, vc_l, length, kv, md.prec)
            x = _mlp_out(p, x, o.reshape(b, 1, h * hd), m, md)
            return x, (kc_l, ks_l, vc_l)

        x, (kc, ksc, vc) = jax.lax.scan(layer, x, (blocks, kc, ksc, vc))
        return (kc, ksc, vc), _logits(params, x[:, 0], m, md)

    _, rest = jax.lax.scan(step, (kc, ksc, vc), jnp.arange(n_new - 1))
    logits = jnp.concatenate([first[None], rest], 0).transpose(1, 0, 2)
    best = jnp.max(logits, -1)
    at = jnp.take_along_axis(logits, served[..., None], -1)[..., 0]
    at_probe = jnp.take_along_axis(logits, probe[..., None], -1)[..., 0]
    return best - at, best - at_probe, jnp.argmax(logits, -1)


_M_KEYS = ("num_hidden_layers", "hidden_size", "num_attention_heads",
           "num_key_value_heads", "intermediate_size", "vocab_size",
           "rope_theta", "rms_norm_eps", "torch_dtype")


def logit_gaps(params, prompts, served, m: dict, kv: dict, *, probe=None,
               fp8: bool = False, lanes_per_call: int = 8):
    """Teacher-forced over `prompts` (N, S) and `served` (N, M) tokens, in
    blocks of `lanes_per_call` lanes (one compiled shape): at each of the
    M positions the gap by which the served token's logit lies below the
    best logit, (N, M); the same for `probe` tokens; and the argmax
    tokens. All three from this mode's own logits."""
    m_items = tuple((k, m[k]) for k in _M_KEYS)
    kv_items = tuple(sorted(kv.items()))
    probe = served if probe is None else probe
    n = len(prompts)
    pad = -n % lanes_per_call
    rows = np.r_[np.arange(n), np.full(pad, n - 1)]
    outs = []
    for i in range(0, n + pad, lanes_per_call):
        sl = rows[i:i + lanes_per_call]
        outs.append([np.asarray(o) for o in _run(
            params, jnp.asarray(prompts[sl], jnp.int32),
            jnp.asarray(served[sl], jnp.int32),
            jnp.asarray(probe[sl], jnp.int32), m_items, kv_items, fp8)])
    return tuple(np.concatenate(x)[:n] for x in zip(*outs))
