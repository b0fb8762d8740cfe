"""Plain reference of the HOLMES ECG zoo, and the weights it shares
with the program.

Nothing here imports the program.  It holds:

* ``member_specs``: the zoo a configuration file describes (HOLMES,
  KDD 2020, §4.1.1: 3 leads x widths x residual blocks of 1-D "stripe"
  ResNeXt, cardinality 8, 7-tap kernels, 30 s at 250 Hz);
* ``init_zoo``: every member's weights from the seed, made on the
  device by one jitted call, in float32 (the type they are served in)
  and in the pytree layout the program's ``ZooMember`` takes;
* ``forward``: one member's forward pass in straightforward
  ``jax.numpy``: stem conv (stride 2) -> GroupNorm -> ReLU, then per
  block a 1x1 reduce, a grouped 7-tap stripe conv (stride 2 on even
  blocks), a 1x1 expand, each followed by GroupNorm, a strided identity
  shortcut and ReLU; global mean pool and a 2-way linear head.  The
  paper's BatchNorm is GroupNorm in the program (no running statistics);
  the reference follows the program there.  Float32 convolutions run at
  the precision the configuration states (``matmul_precision``: the
  chip's default, one bfloat16 pass with float32 accumulation), or at
  ``highest`` for a reading beside it; ``dtype=bfloat16`` is the
  lower-precision control, every array and every product in bfloat16;
* ``member_scores``: P(stable) per member on given windows, at a
  precision or in a dtype (``bfloat16``: the control).

Eq. 5 and the side models, which every family is served through, are
in ``side_reference.py``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence

import numpy as np


@dataclasses.dataclass(frozen=True)
class Member:
    name: str
    lead: int
    width: int
    blocks: int
    input_len: int
    cardinality: int
    kernel_size: int

    @property
    def inner(self) -> int:
        inner = max(self.cardinality, self.width // 2)
        return inner - inner % self.cardinality


def member_specs(cfg: Dict) -> List[Member]:
    """Members in the program's zoo order: lead, then width, then blocks."""
    L = int(cfg["window_s"] * cfg["ecg_hz"])
    out = []
    for lead in range(cfg["leads"]):
        for w in cfg["widths"]:
            for b in cfg["blocks"]:
                out.append(Member(f"lead{lead + 1}_w{w}_b{b}", lead, w, b, L,
                                  min(cfg["cardinality"], w),
                                  cfg["kernel_size"]))
    return out


# --------------------------------------------------------------- weights
def _member_init(m: Member, tn, nrm):
    """One member's weights; ``tn(shape, scale)`` hands out a
    truncated-normal (in [-2, 2]) draw times ``scale``, ``nrm(shape,
    scale, offset)`` a normal one times ``scale`` plus ``offset``."""
    def conv(k, cin, cout, groups=1):
        return {"w": tn((k, cin // groups, cout),
                        1.0 / np.sqrt(k * cin // groups)),
                "b": nrm((cout,), 0.1, 0.0)}

    def gn(c):
        return {"scale": nrm((c,), 0.1, 1.0), "bias": nrm((c,), 0.1, 0.0)}

    W, inner = m.width, m.inner
    p = {"stem": conv(m.kernel_size, 1, W), "stem_gn": gn(W), "blocks": []}
    for _ in range(m.blocks):
        p["blocks"].append({
            "reduce": conv(1, W, inner), "gn1": gn(inner),
            "stripe": conv(m.kernel_size, inner, inner, m.cardinality),
            "gn2": gn(inner),
            "expand": conv(1, inner, W), "gn3": gn(W)})
    p["head"] = {"w": tn((W, 2), 1.0 / np.sqrt(W)), "b": nrm((2,), 0.1, 0.0)}
    return p


def seed_key(seed: int):
    """A PRNG key from any whole seed, 64 bits of it."""
    import jax
    s = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.wrap_key_data(np.asarray(s, np.uint32),
                                    impl="threefry2x32")


def init_zoo(members: Sequence[Member], seed: int, device=None):
    """All members' weights from the seed, drawn on ``device`` by one
    jitted call: one draw per kind of leaf (its shape and scale), stacked
    over every leaf of that kind, then handed out leaf by leaf.  A
    random call per leaf would unroll thousands of generators into the
    program, which then takes minutes and tens of GiB to compile."""
    import jax
    import jax.numpy as jnp

    kinds: Dict[tuple, int] = {}

    def count(dist):
        def draw(shape, *affine):
            key = (dist, tuple(shape)) + affine
            kinds[key] = kinds.get(key, 0) + 1
        return draw
    for m in members:
        _member_init(m, count("tn"), count("n"))
    order = sorted(kinds)

    @jax.jit
    def make(key):
        out = []
        for i, (dist, shape, *affine) in enumerate(order):
            k = jax.random.fold_in(key, i)
            full = (kinds[(dist, shape) + tuple(affine)],) + shape
            if dist == "tn":
                out.append(jax.random.truncated_normal(
                    k, -2.0, 2.0, full, jnp.float32) * affine[0])
            else:
                out.append(jax.random.normal(k, full, jnp.float32)
                           * affine[0] + affine[1])
        return out

    key = seed_key(seed)
    if device is not None:
        key = jax.device_put(key, device)
    # unstacked a kind at a time: a few dozen calls, not one per leaf
    leaves = {k: iter(list(a)) for k, a in zip(order, make(key))}

    def take(dist):
        def draw(shape, *affine):
            return next(leaves[(dist, tuple(shape)) + affine])
        return draw
    return [_member_init(m, take("tn"), take("n")) for m in members]


# --------------------------------------------------------------- forward
def _conv(x, w, b, stride, groups, dtype, prec):
    import jax
    y = jax.lax.conv_general_dilated(
        x, w.astype(dtype), window_strides=(stride,), padding="SAME",
        dimension_numbers=("NHC", "HIO", "NHC"),
        feature_group_count=groups, precision=prec,
        preferred_element_type=dtype)
    return y + b.astype(dtype)


def _group_norm(p, x, dtype, groups: int = 4, eps: float = 1e-5):
    import jax
    import jax.numpy as jnp
    B, L, C = x.shape
    g = min(groups, C)
    while C % g:
        g -= 1
    xg = x.reshape(B, L, g, C // g)
    mu = jnp.mean(xg, axis=(1, 3), keepdims=True, dtype=dtype)
    var = jnp.mean(jnp.square(xg - mu), axis=(1, 3), keepdims=True,
                   dtype=dtype)
    xg = (xg - mu) * jax.lax.rsqrt(var + jnp.asarray(eps, dtype))
    return (xg.reshape(B, L, C) * p["scale"].astype(dtype)
            + p["bias"].astype(dtype))


def forward(p: Dict, x, m: Member, dtype, precision: str = "default"):
    """x: [B, L, 1] one lead's window -> logits [B, 2] in ``dtype``;
    convolutions and the head at ``precision`` (``default`` or
    ``highest``)."""
    import jax
    import jax.numpy as jnp
    relu = jax.nn.relu
    prec = getattr(jax.lax.Precision, precision.upper())
    h = _conv(x.astype(dtype), p["stem"]["w"], p["stem"]["b"], 2, 1, dtype,
              prec)
    h = relu(_group_norm(p["stem_gn"], h, dtype))
    for i, blk in enumerate(p["blocks"]):
        stride = 2 if i % 2 == 0 else 1
        r = _conv(h, blk["reduce"]["w"], blk["reduce"]["b"], 1, 1, dtype,
                  prec)
        r = relu(_group_norm(blk["gn1"], r, dtype))
        r = _conv(r, blk["stripe"]["w"], blk["stripe"]["b"], stride,
                  m.cardinality, dtype, prec)
        r = relu(_group_norm(blk["gn2"], r, dtype))
        r = _conv(r, blk["expand"]["w"], blk["expand"]["b"], 1, 1, dtype,
                  prec)
        r = _group_norm(blk["gn3"], r, dtype)
        short = h[:, ::stride][:, :r.shape[1]]
        h = relu(short + r)
    pooled = jnp.mean(h, axis=1, dtype=dtype)
    return (jnp.dot(pooled, p["head"]["w"].astype(dtype), precision=prec,
                    preferred_element_type=dtype)
            + p["head"]["b"].astype(dtype))


@functools.lru_cache(maxsize=None)
def _jitted(m: Member, dtype_name: str, precision: str):
    import jax
    import jax.numpy as jnp
    dtype = jnp.dtype(dtype_name)

    @jax.jit
    def f(p, x):
        logits = forward(p, x, m, dtype, precision).astype(jnp.float32)
        return jax.nn.sigmoid(logits[:, 1] - logits[:, 0])
    return f


def member_scores(params: Sequence[Dict], members: Sequence[Member],
                  windows: np.ndarray, *, dtype: str = "float32",
                  precision: str = "default",
                  devices: Optional[Sequence] = None,
                  block: int = 64) -> np.ndarray:
    """[M, K] float64 P(stable) of every member on every window.

    ``windows`` is [K, 3, L] float32.  Members go round-robin over
    ``devices`` and rows in blocks of ``block``; all calls are issued
    before the first result is read."""
    import jax
    import jax.numpy as jnp
    devices = list(devices) if devices else [None]
    K = windows.shape[0]
    pend = []
    placed = {}
    for i, (p, m) in enumerate(zip(params, members)):
        dev = devices[i % len(devices)]
        f = _jitted(dataclasses.replace(m, name="", lead=0), dtype,
                    precision)
        pd = jax.device_put(p, dev) if dev is not None else p
        for r0 in range(0, K, block):
            xb = windows[r0:r0 + block, m.lead, -m.input_len:]
            if xb.shape[0] < block:       # one compiled shape per member
                xb = np.pad(xb, ((0, block - xb.shape[0]), (0, 0)))
            key = (dev, m.lead, r0)
            if key not in placed:
                arr = jnp.asarray(xb[..., None])
                placed[key] = (jax.device_put(arr, dev) if dev is not None
                               else arr)
            pend.append((i, r0, f(pd, placed[key])))
    out = np.zeros((len(members), K))
    for i, r0, y in pend:
        y = np.asarray(y, np.float64)
        n = min(block, K - r0)
        out[i, r0:r0 + n] = y[:n]
    return out
