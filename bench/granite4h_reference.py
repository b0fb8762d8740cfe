"""Plain reference of the HOLMES zoo with a Granite-4.0-H hybrid member,
and the hybrid's weights, shared with the program.

Nothing here imports the program.  The zoo's members go to
``reference.py`` unchanged; this file holds the hybrid:

* ``hybrid_spec``: the member a configuration file describes, from the
  keys of the published Granite-4.0-H config (hidden size, Mamba-2 and
  GQA widths, chunk, multipliers, eps, ``layer_types`` cut to
  ``num_hidden_layers``) and the ECG input it reads (``member``);
* ``init_hybrid``: its weights from the seed, made on the device by one
  jitted call, float32, in the pytree layout the program's
  ``HybridMember`` takes;
* ``forward``: the stack in straightforward ``jax.numpy``.  Per layer
  ``h += r * mixer(rms(h))`` then ``h += r * mlp(rms(h))``, r the
  residual multiplier; the Mamba-2 mixer projects z, x, B, C, dt,
  runs a causal depthwise conv (bias, SiLU) over x, B and C, then the
  SSD as its **sequential per-token recurrence** ``h_t = exp(dt_t A)
  h_{t-1} + dt_t x_t B_t^T``, ``y_t = C_t h_t + D x_t`` (no chunks),
  a gated RMSNorm over d_inner (``rms(y * silu(z))``, one group) and
  the out projection; the attention mixer is a plain causally masked
  softmax over 32 query and 8 shared KV heads, no positional encoding,
  scores times ``attention_multiplier``; the MLP is SwiGLU.
* ``member_scores``: P(stable) of every member on given windows.

Departures from the published description (each also in the
configuration's ``assumed``): the 100,352-row token embedding gives way
to a linear embedding of 0.1 s x 3-lead patches (75 values, 300 tokens
per 30 s window), still times ``embedding_multiplier``; the tied LM
head (and its ``logits_scaling``) to a 2048 x 2 head on the last token,
drawn with std ``head_std`` / sqrt(2048); the depth to the first
``num_hidden_layers`` layers.  The in-projection is held as separate
z, x, B, C, dt matrices (the published fused one, split), as the
program holds it.

Precision: float32 at the ``precision`` the configuration states
(``default``: the chip's one bfloat16 pass, float32 accumulation) or
``highest``; ``dtype=bfloat16`` is the lower-precision control, every
array and every product in bfloat16.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

import reference as _zoo


@dataclasses.dataclass(frozen=True)
class Hybrid:
    name: str
    layer_types: Tuple[str, ...]
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    ssm_heads: int
    ssm_head_dim: int
    d_state: int
    n_groups: int
    d_conv: int
    chunk: int
    attention_multiplier: float
    residual_multiplier: float
    embedding_multiplier: float
    norm_eps: float
    leads: int
    patch: int
    input_len: int
    head_std: float
    kind = "hybrid"

    @property
    def tokens(self) -> int:
        return self.input_len // self.patch

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim


def hybrid_spec(cfg: Dict) -> Hybrid:
    """The hybrid member of a configuration (its Granite keys and its
    ``member`` group)."""
    m = cfg["member"]
    d = cfg["hidden_size"]
    return Hybrid(
        name="granite4h",
        layer_types=tuple(cfg["layer_types"][:cfg["num_hidden_layers"]]),
        d_model=d, n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=d // cfg["num_attention_heads"],
        d_ff=cfg["shared_intermediate_size"],
        ssm_heads=cfg["mamba_n_heads"], ssm_head_dim=cfg["mamba_d_head"],
        d_state=cfg["mamba_d_state"], n_groups=cfg["mamba_n_groups"],
        d_conv=cfg["mamba_d_conv"], chunk=cfg["mamba_chunk_size"],
        attention_multiplier=cfg["attention_multiplier"],
        residual_multiplier=cfg["residual_multiplier"],
        embedding_multiplier=float(cfg["embedding_multiplier"]),
        norm_eps=cfg["rms_norm_eps"], leads=cfg["leads"],
        patch=m["patch_samples"],
        input_len=int(cfg["window_s"] * cfg["ecg_hz"]),
        head_std=m["head_std"])


# --------------------------------------------------------------- weights
def _hybrid_init(m: Hybrid, tn, nrm, unif):
    """The member's weights; ``tn(shape, scale)`` a truncated normal (in
    [-2, 2]) times ``scale``, ``nrm(shape, scale, offset)`` a normal,
    ``unif(shape, lo, hi)`` a uniform draw."""
    d, di, H = m.d_model, m.d_inner, m.ssm_heads
    gn = m.n_groups * m.d_state

    def lin(din, dout):
        return {"w": tn((din, dout), 1.0 / np.sqrt(din))}

    def norm(n):
        return {"scale": nrm((n,), 0.1, 1.0)}

    def mamba():
        # A in [1, 16] and dt in [1e-3, 1e-1] log-uniform, as Mamba-2
        # draws them; dt_bias = softplus^-1(dt)
        dt = np.exp(unif((H,), np.log(1e-3), np.log(1e-1)))
        return {
            "z_proj": tn((d, di), 1 / np.sqrt(d)),
            "x_proj": tn((d, di), 1 / np.sqrt(d)),
            "B_proj": tn((d, gn), 1 / np.sqrt(d)),
            "C_proj": tn((d, gn), 1 / np.sqrt(d)),
            "dt_proj": tn((d, H), 1 / np.sqrt(d)),
            "conv_x": tn((m.d_conv, 1, di), 1 / np.sqrt(m.d_conv)),
            "conv_B": tn((m.d_conv, 1, gn), 1 / np.sqrt(m.d_conv)),
            "conv_C": tn((m.d_conv, 1, gn), 1 / np.sqrt(m.d_conv)),
            "conv_bx": nrm((di,), 0.1, 0.0),
            "conv_bB": nrm((gn,), 0.1, 0.0),
            "conv_bC": nrm((gn,), 0.1, 0.0),
            "A_log": np.log(unif((H,), 1.0, 16.0)),
            "D": nrm((H,), 0.1, 1.0),
            "dt_bias": dt + np.log(-np.expm1(-dt)),
            "norm": norm(di),
            "out_proj": tn((di, d), 1 / np.sqrt(di))}

    def gqa():
        q, kv = m.n_heads * m.head_dim, m.n_kv_heads * m.head_dim
        return {"wq": lin(d, q), "wk": lin(d, kv), "wv": lin(d, kv),
                "wo": lin(q, d)}

    layers = [{"ln1": norm(d),
               "mixer": mamba() if kind == "mamba" else gqa(),
               "ln2": norm(d),
               "mlp": {"gate": lin(d, m.d_ff), "up": lin(d, m.d_ff),
                       "down": lin(m.d_ff, d)}}
              for kind in m.layer_types]
    pd = m.leads * m.patch
    # unit scale into layer 0 after the published embedding multiplier
    return {"embed": {"w": tn((pd, d),
                              1 / (m.embedding_multiplier * np.sqrt(pd))),
                      "b": nrm((d,), 0.1, 0.0)},
            "layers": layers,
            "final_norm": norm(d),
            "head": {"w": tn((d, 2), m.head_std / np.sqrt(d)),
                     "b": nrm((2,), 0.1, 0.0)}}


class _Draw:
    """Leaf draws by kind (distribution, shape, parameters): counted on
    a first pass, drawn stacked per kind by one jitted call, handed out
    on a second pass.  ``np.log``/``np.exp`` of a draw act on the jax
    array it stands for."""

    def __init__(self):
        self.kinds: Dict[tuple, int] = {}
        self.leaves = None

    def __call__(self, dist):
        def draw(shape, *par):
            key = (dist, tuple(shape)) + tuple(float(p) for p in par)
            if self.leaves is None:
                self.kinds[key] = self.kinds.get(key, 0) + 1
                return np.zeros(shape, np.float32)
            return next(self.leaves[key])
        return draw


def init_hybrid(m: Hybrid, seed: int, device=None) -> Dict:
    """The hybrid member's weights from the seed (a stream of its own,
    apart from the zoo's), made on ``device``."""
    import jax
    import jax.numpy as jnp

    d = _Draw()
    with np.errstate(divide="ignore"):
        _hybrid_init(m, d("tn"), d("n"), d("u"))
    order = sorted(d.kinds)

    @jax.jit
    def make(key):
        out = []
        for i, (dist, shape, *par) in enumerate(order):
            k = jax.random.fold_in(key, i)
            full = (d.kinds[(dist, shape) + tuple(par)],) + shape
            if dist == "tn":
                out.append(jax.random.truncated_normal(
                    k, -2.0, 2.0, full, jnp.float32) * par[0])
            elif dist == "n":
                out.append(jax.random.normal(k, full, jnp.float32)
                           * par[0] + par[1])
            else:
                out.append(jax.random.uniform(k, full, jnp.float32,
                                              par[0], par[1]))
        return out

    key = jax.random.fold_in(_zoo.seed_key(seed), 0x6A4)
    if device is not None:
        key = jax.device_put(key, device)
    d.leaves = {k: iter(list(a)) for k, a in zip(order, make(key))}
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32),
                        _hybrid_init(m, d("tn"), d("n"), d("u")))


# --------------------------------------------------------------- forward
def _dot(a, w, dtype, prec):
    import jax.numpy as jnp
    return jnp.matmul(a, w.astype(dtype), precision=prec,
                      preferred_element_type=dtype)


def _rms(x, scale, eps, dtype):
    import jax
    import jax.numpy as jnp
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True, dtype=dtype)
    return x * jax.lax.rsqrt(ms + jnp.asarray(eps, dtype)) \
        * scale.astype(dtype)


def _causal_conv(x, w, b, dtype):
    """Depthwise causal conv over tokens: x [K, T, ch], w [k, 1, ch]."""
    import jax.numpy as jnp
    k = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    T = x.shape[1]
    out = sum(xp[:, j:j + T] * w[j, 0].astype(dtype) for j in range(k))
    return out + b.astype(dtype)


def _mamba(p, u, m: Hybrid, dtype, prec):
    import jax
    import jax.numpy as jnp
    K, T, _ = u.shape
    H, P, N, G = m.ssm_heads, m.ssm_head_dim, m.d_state, m.n_groups
    z = _dot(u, p["z_proj"], dtype, prec)
    silu = jax.nn.silu
    x = silu(_causal_conv(_dot(u, p["x_proj"], dtype, prec), p["conv_x"],
                          p["conv_bx"], dtype))
    Bm = silu(_causal_conv(_dot(u, p["B_proj"], dtype, prec), p["conv_B"],
                           p["conv_bB"], dtype))
    Cm = silu(_causal_conv(_dot(u, p["C_proj"], dtype, prec), p["conv_C"],
                           p["conv_bC"], dtype))
    dt = jax.nn.softplus(_dot(u, p["dt_proj"], dtype, prec)
                         + p["dt_bias"].astype(dtype))             # [K,T,H]
    A = -jnp.exp(p["A_log"].astype(dtype))
    D = p["D"].astype(dtype)
    rep = H // G
    xs = x.reshape(K, T, H, P)
    Bh = jnp.repeat(Bm.reshape(K, T, G, N), rep, axis=2)          # [K,T,H,N]
    Ch = jnp.repeat(Cm.reshape(K, T, G, N), rep, axis=2)

    def step(h, t):
        x_t, dt_t, B_t, C_t = t
        h = h * jnp.exp(dt_t * A)[:, :, None, None] \
            + (dt_t[:, :, None] * x_t)[..., None] * B_t[:, :, None, :]
        y = jnp.einsum("khpn,khn->khp", h, C_t, precision=prec,
                       preferred_element_type=dtype) + D[:, None] * x_t
        return h, y

    seq = tuple(jnp.moveaxis(a, 1, 0) for a in (xs, dt, Bh, Ch))
    _, y = jax.lax.scan(step, jnp.zeros((K, H, P, N), dtype), seq)
    y = jnp.moveaxis(y, 0, 1).reshape(K, T, H * P)
    y = _rms(y * silu(z), p["norm"]["scale"], m.norm_eps, dtype)
    return _dot(y, p["out_proj"], dtype, prec)


def _attention(p, u, m: Hybrid, dtype, prec):
    import jax
    import jax.numpy as jnp
    K, T, _ = u.shape
    hd, g = m.head_dim, m.n_heads // m.n_kv_heads
    q = _dot(u, p["wq"]["w"], dtype, prec).reshape(K, T, m.n_heads, hd)
    k = jnp.repeat(_dot(u, p["wk"]["w"], dtype, prec).reshape(
        K, T, m.n_kv_heads, hd), g, axis=2)
    v = jnp.repeat(_dot(u, p["wv"]["w"], dtype, prec).reshape(
        K, T, m.n_kv_heads, hd), g, axis=2)
    s = jnp.einsum("kthd,kshd->khts", q, k, precision=prec,
                   preferred_element_type=dtype) \
        * jnp.asarray(m.attention_multiplier, dtype)
    causal = np.tril(np.ones((T, T), bool))
    s = jnp.where(causal, s, jnp.asarray(-jnp.inf, dtype))
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("khts,kshd->kthd", w, v, precision=prec,
                   preferred_element_type=dtype)
    return _dot(o.reshape(K, T, m.n_heads * hd), p["wo"]["w"], dtype, prec)


def forward(p: Dict, win, m: Hybrid, dtype, precision: str = "default"):
    """win: [K, leads, L] -> logits [K, 2] in ``dtype``."""
    import jax
    prec = getattr(jax.lax.Precision, precision.upper())
    K = win.shape[0]
    x = win[:, :, -m.input_len:].astype(dtype).reshape(
        K, m.leads, m.tokens, m.patch).transpose(0, 2, 1, 3).reshape(
        K, m.tokens, m.leads * m.patch)
    h = (_dot(x, p["embed"]["w"], dtype, prec) + p["embed"]["b"].astype(
        dtype)) * dtype(m.embedding_multiplier)
    r = dtype(m.residual_multiplier)
    for kind, lp in zip(m.layer_types, p["layers"]):
        u = _rms(h, lp["ln1"]["scale"], m.norm_eps, dtype)
        mix = _mamba if kind == "mamba" else _attention
        h = h + r * mix(lp["mixer"], u, m, dtype, prec)
        u = _rms(h, lp["ln2"]["scale"], m.norm_eps, dtype)
        mlp = lp["mlp"]
        h = h + r * _dot(jax.nn.silu(_dot(u, mlp["gate"]["w"], dtype, prec))
                         * _dot(u, mlp["up"]["w"], dtype, prec),
                         mlp["down"]["w"], dtype, prec)
    last = _rms(h[:, -1], p["final_norm"]["scale"], m.norm_eps, dtype)
    return _dot(last, p["head"]["w"], dtype, prec) \
        + p["head"]["b"].astype(dtype)


@functools.lru_cache(maxsize=None)
def _jitted(m: Hybrid, dtype_name: str, precision: str):
    import jax
    import jax.numpy as jnp
    dtype = jnp.dtype(dtype_name).type

    @jax.jit
    def f(p, x):
        logits = forward(p, x, m, dtype, precision).astype(jnp.float32)
        return jax.nn.sigmoid(logits[:, 1] - logits[:, 0])
    return f


def member_scores(params: Sequence[Dict], members: Sequence,
                  windows: np.ndarray, *, dtype: str = "float32",
                  precision: str = "default",
                  devices: Optional[Sequence] = None,
                  block: int = 32) -> np.ndarray:
    """[M, K] float64 P(stable) of every member on every window
    ([K, 3, L] float32): the zoo's through ``reference.py``, the
    hybrid's here, in blocks of ``block`` windows on the first device."""
    import jax
    import jax.numpy as jnp
    hyb = [i for i, m in enumerate(members)
           if getattr(m, "kind", None) == "hybrid"]
    zoo = [i for i in range(len(members)) if i not in hyb]
    out = np.zeros((len(members), windows.shape[0]))
    if zoo:
        out[zoo] = _zoo.member_scores(
            [params[i] for i in zoo], [members[i] for i in zoo], windows,
            dtype=dtype, precision=precision, devices=devices)
    dev = list(devices)[0] if devices else None
    K = windows.shape[0]
    for i in hyb:
        f = _jitted(members[i], dtype, precision)
        p = jax.device_put(params[i], dev) if dev is not None \
            else params[i]
        for r0 in range(0, K, block):
            xb = windows[r0:r0 + block]
            n = xb.shape[0]
            if n < block:                 # one compiled shape per member
                xb = np.pad(xb, ((0, block - n), (0, 0), (0, 0)))
            x = jnp.asarray(xb)
            if dev is not None:
                x = jax.device_put(x, dev)
            out[i, r0:r0 + n] = np.asarray(f(p, x), np.float64)[:n]
    return out
