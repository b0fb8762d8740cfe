"""Plain reference of the HOLMES ensemble, and the weights it shares
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
* ``member_scores`` / ``ensemble_scores``: P(stable) per member and the
  bagged mean (Eq. 5), with the side models where the configuration
  has them;
* ``RandomForest`` / ``VitalsForest`` / ``LogisticRegression``: copies
  of the program's numpy side models, fitted here on the same cohort
  with the same seeds, so the reference takes no fitted table from the
  program.
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


def ensemble_scores(member_mat: np.ndarray,
                    side: Sequence[np.ndarray] = ()) -> np.ndarray:
    """Eq. 5: the mean over members (rows), side-model scores appended
    as further rows."""
    rows = [member_mat] + [np.asarray(s, np.float64)[None] for s in side]
    return np.mean(np.concatenate(rows, axis=0), axis=0)


# ------------------------------------------------------- side models
class DecisionTree:
    def __init__(self, max_depth: int = 8, min_samples_leaf: int = 2,
                 max_features: Optional[int] = None, rng=None):
        self.max_depth = max_depth
        self.min_samples_leaf = min_samples_leaf
        self.max_features = max_features
        self.rng = rng or np.random.default_rng(0)
        self.nodes: List[list] = []      # [feature, threshold, l, r, value]

    def fit(self, X, y):
        self.nodes = []
        self._grow(np.asarray(X, np.float64), np.asarray(y, np.float64), 0)
        return self

    def _grow(self, X, y, depth) -> int:
        idx = len(self.nodes)
        self.nodes.append([-1, 0.0, -1, -1, float(np.mean(y))])
        n, d = X.shape
        if depth >= self.max_depth or n < 2 * self.min_samples_leaf \
                or np.all(y == y[0]):
            return idx
        k = self.max_features or max(1, int(np.sqrt(d)))
        feats = self.rng.choice(d, size=min(k, d), replace=False)
        best = (0.0, -1, 0.0)
        total_sum, total_sq = y.sum(), (y ** 2).sum()
        base = total_sq - total_sum ** 2 / n
        for f in feats:
            order = np.argsort(X[:, f], kind="stable")
            xs, ys = X[order, f], y[order]
            csum = np.cumsum(ys)[:-1]
            csq = np.cumsum(ys ** 2)[:-1]
            nl = np.arange(1, n)
            valid = xs[1:] != xs[:-1]
            nl_f = nl.astype(np.float64)
            sse = ((csq - csum ** 2 / nl_f)
                   + (total_sq - csq) - (total_sum - csum) ** 2 / (n - nl_f))
            sse = np.where(valid & (nl >= self.min_samples_leaf)
                           & (n - nl >= self.min_samples_leaf), sse, np.inf)
            j = int(np.argmin(sse))
            gain = base - sse[j]
            if np.isfinite(sse[j]) and gain > best[0] + 1e-12:
                best = (gain, f, (xs[j] + xs[j + 1]) / 2.0)
        if best[1] < 0:
            return idx
        _, f, thr = best
        mask = X[:, f] <= thr
        self.nodes[idx][0] = f
        self.nodes[idx][1] = thr
        self.nodes[idx][2] = self._grow(X[mask], y[mask], depth + 1)
        self.nodes[idx][3] = self._grow(X[~mask], y[~mask], depth + 1)
        return idx

    def predict(self, X) -> np.ndarray:
        X = np.asarray(X, np.float64)
        out = np.empty(len(X))
        for i, row in enumerate(X):
            node = self.nodes[0]
            while node[0] >= 0:
                node = self.nodes[node[2] if row[node[0]] <= node[1]
                                  else node[3]]
            out[i] = node[4]
        return out


class RandomForest:
    def __init__(self, n_trees: int, max_depth: int, seed: int,
                 min_samples_leaf: int = 2):
        self.n_trees, self.max_depth, self.seed = n_trees, max_depth, seed
        self.min_samples_leaf = min_samples_leaf
        self.trees: List[DecisionTree] = []

    def fit(self, X, y):
        X = np.asarray(X, np.float64)
        y = np.asarray(y, np.float64)
        rng = np.random.default_rng(self.seed)
        n = len(X)
        self.trees = []
        for _ in range(self.n_trees):
            boot = rng.integers(0, n, size=n)
            self.trees.append(DecisionTree(
                self.max_depth, self.min_samples_leaf, None, rng
            ).fit(X[boot], y[boot]))
        return self

    def predict(self, X) -> np.ndarray:
        return np.mean([t.predict(X) for t in self.trees], axis=0)


class VitalsForest:
    """One forest per vital sign, their predictions averaged."""

    def __init__(self, n_channels: int, n_trees: int, max_depth: int,
                 seed: int):
        self.models = [RandomForest(n_trees, max_depth, seed + i)
                       for i in range(n_channels)]

    def fit(self, X, y):
        for c, m in enumerate(self.models):
            m.fit(X[:, c, :], y)
        return self

    def predict_proba(self, X) -> np.ndarray:
        return np.clip(np.mean([m.predict(X[:, c, :])
                                for c, m in enumerate(self.models)],
                               axis=0), 0.0, 1.0)


class LogisticRegression:
    def __init__(self, lr: float, steps: int, l2: float, seed: int):
        self.lr, self.steps, self.l2, self.seed = lr, steps, l2, seed

    def fit(self, X, y):
        X = np.asarray(X, np.float64)
        y = np.asarray(y, np.float64)
        mu, sd = X.mean(0), X.std(0) + 1e-8
        self._norm = (mu, sd)
        Xn = (X - mu) / sd
        rng = np.random.default_rng(self.seed)
        self.w = rng.normal(0, 0.01, X.shape[1])
        self.b = 0.0
        for _ in range(self.steps):
            p = self._sigmoid(Xn @ self.w + self.b)
            g = Xn.T @ (p - y) / len(y) + self.l2 * self.w
            self.w -= self.lr * g
            self.b -= self.lr * float(np.mean(p - y))
        return self

    @staticmethod
    def _sigmoid(z):
        return 1.0 / (1.0 + np.exp(-np.clip(z, -30, 30)))

    def predict_proba(self, X) -> np.ndarray:
        mu, sd = self._norm
        return self._sigmoid(((np.asarray(X, np.float64) - mu) / sd)
                             @ self.w + self.b)
