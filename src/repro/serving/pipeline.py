"""The served ensemble pipeline (Fig. 4): HTTP-ingest stand-in ->
stateful aggregators -> ensemble query -> bagging combine.

``EnsembleService`` does real jitted inference with the selected ECG zoo
members plus the CPU-side vitals/labs models; ``StreamingPipeline`` drives
it from per-patient multi-modal streams and records end-to-end wall-clock
latencies (the measured counterpart of the DES simulator).

Fused serving (the hot path)
----------------------------
By default the service executes the zoo in **architecture buckets**
(``configs.ecg_zoo.bucket_zoo``): members with identical shapes — leads
differ only in which input slice they consume — are stacked along a
leading member axis (``launch.ensemble_parallel.stack_members``) and run
as ONE ``ecg_apply_stacked`` dispatch per bucket, so a query costs
``n_buckets`` jitted calls (4 on the reduced 12-member zoo, 20 on the
full 60) instead of ``n_members``.  ``predict_batch`` additionally
micro-batches windows from MANY patients into the same stacked call.
The per-member loop is kept (``fused=False``) as the equivalence oracle
and for per-member cost measurement (``measured_costs``).

The one-transfer-per-device flush contract
------------------------------------------
A flush ships each patient's raw ``[ECG_LEADS, L]`` window to a device
AT MOST ONCE — never once per stacked member.  The host builds one
``[Ppad, ECG_LEADS, L]`` window pack per distinct input length (a
single O(P) pass; left-zero-padding of short windows and pow2 batch
padding land here), transfers it once per device that hosts a bucket
shard, and every bucket's jitted dispatch does its own **lead-gather**
on device: the bucket's static lead indices select member rows out of
the shared pack inside the same XLA program as the stacked forward
pass, so the old O(M x P) per-(member, patient) host marshaling loop —
and its M-times-redundant H2D traffic (M x L floats per patient
instead of ECG_LEADS x L) — is gone.  With **device-resident ingest**
(``serving.aggregator.DeviceIngest``), a batch of
``DeviceWindowRef``s skips even that single transfer: the pack is
gathered straight out of the on-device ring buffers
(``gather_windows``), and only the flushed (patient, end, valid) int32
triples cross the host boundary.  The pre-refactor marshaling loop is
preserved as ``marshal="legacy"`` — the ingest microbench's baseline
and a second equivalence oracle.  ``h2d_bytes`` / ``marshal_seconds``
counters account both regimes for ``BENCH_serving.json["ingest"]``.

Member kinds
------------
The service takes members of more than one kind.  Each kind supplies the
member protocol: ``bucket_key()`` (members with equal keys share one
stacked program), ``stack(params)`` (the bucket's weights as its program
takes them), ``bucket_program(group, impl, marshal)`` (a cached jitted
``(stacked, win [Ppad, 3, L]) -> scores [M, Ppad]`` over the shared window
pack), ``input_len``, ``cost()`` (MACs per window, for LPT) and
``tokens`` (sequence positions per window; 0 for a member that is not a
sequence model).  ``ZooMember`` (a stacked 1-D ResNeXt, programs named
``fn``) and ``HybridMember`` (a Granite-4.0-H Mamba-2 + GQA stack,
program ``hybrid_member``) implement it; the bucket plan, warm-up and
placement read only the protocol.

Multi-device sharded serving (``placement=``)
---------------------------------------------
A ``serving.placement.Placement`` shards the stacked bucket params
across ``jax.devices()``: each placement slot's members are bucketed
independently and every (bucket, device) shard gets its own
``device_put``-pinned stacked pytree, so a flush issues one stacked
dispatch per shard — all async, on their own devices — and the scores
are combined by a single host-side gather at the end (the cross-device
gather/sum of Eq. 5).  Placement is controller-actuated state:
``control.swap.HotSwapper`` stages ``(selector, placement)`` pairs and
the adaptive controller re-derives the LPT plan from freshly measured
bucket costs (``measured_bucket_costs`` -> ``plan_placement``).
"""
from __future__ import annotations

import dataclasses
import functools
import threading
import time
from typing import Callable, Dict, Hashable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.ecg_zoo import (CLIP_SECONDS, ECG_HZ, ECG_LEADS,
                                   EcgModelSpec, VITALS_HZ, bucket_key)
from repro.configs.granite_4h import GraniteHybridSpec
from repro.obs import spans as _spans
from repro.launch.ensemble_parallel import stack_members
from repro.models.ecg_resnext import ecg_apply, ecg_apply_stacked, ecg_macs
from repro.models.granite_hybrid import granite_apply_stacked, granite_macs
from repro.serving.aggregator import (DeviceIngest, DeviceWindowRef,
                                      ModalitySpec, PatientAggregator,
                                      gather_windows, pow2_rung)
from repro.serving.placement import (Placement, grouped_lpt_placement,
                                     lpt_placement)


@dataclasses.dataclass
class ZooMember:
    """A 1-D stripe ResNeXt of the paper's zoo: one lead, stacked with
    the members of its ``bucket_key`` into one program named ``fn``."""
    spec: EcgModelSpec
    params: Dict
    kind = "resnext"
    tokens = 0

    @property
    def input_len(self) -> int:
        return self.spec.input_len

    def bucket_key(self) -> Hashable:
        return bucket_key(self.spec)

    def cost(self) -> float:
        return ecg_macs(self.spec)

    @staticmethod
    def stack(params: Sequence[Dict]) -> Dict:
        return stack_members(list(params))

    def bucket_program(self, group: Sequence["ZooMember"], impl: str,
                       marshal: str) -> Callable:
        return _make_bucket_fn(self.spec, [m.spec.lead for m in group],
                               impl, marshal)


@dataclasses.dataclass
class HybridMember:
    """A Granite-4.0-H hybrid (Mamba-2 + NoPE GQA) over all three leads'
    0.1 s patches, scored by its last token; its program is named
    ``hybrid_member``.  The SSD scan runs in the Pallas kernel on a TPU
    and in its jnp form elsewhere."""
    spec: GraniteHybridSpec
    params: Dict
    kind = "hybrid"

    @property
    def input_len(self) -> int:
        return self.spec.input_len

    @property
    def tokens(self) -> int:
        return self.spec.tokens

    @staticmethod
    def _ssd() -> str:
        return "pallas" if jax.default_backend() == "tpu" else "xla"

    def bucket_key(self) -> Hashable:
        return ("hybrid", self.spec, self._ssd())

    def cost(self) -> float:
        return granite_macs(self.spec)

    @staticmethod
    def stack(params: Sequence[Dict]) -> Tuple[Dict, ...]:
        # held as they are: stacking gigabytes of weights would copy them
        return tuple(params)

    def bucket_program(self, group: Sequence["HybridMember"], impl: str,
                       marshal: str) -> Callable:
        if marshal != "packed":
            raise ValueError(f"a {self.kind} member needs the packed "
                             f"marshal, not {marshal!r}")
        return _make_hybrid_fn(self.spec, self._ssd())


def bucket_members(members: Sequence) -> Dict[Hashable, List[int]]:
    """Member indices grouped by ``bucket_key()``, insertion-ordered
    (for ResNeXt members exactly ``configs.ecg_zoo.bucket_zoo``)."""
    out: Dict[Hashable, List[int]] = {}
    for i, m in enumerate(members):
        out.setdefault(m.bucket_key(), []).append(i)
    return out


def _resnext_only(members: Sequence, what: str) -> None:
    kinds = sorted({m.kind for m in members} - {"resnext"})
    if kinds:
        raise ValueError(f"{what} runs ResNeXt programs only; {kinds} "
                         f"members serve on the fused packed path")


@dataclasses.dataclass
class _Bucket:
    """One stacked-execution group: structurally identical members.
    With a placement this is a (bucket, device) SHARD — the same bucket
    may appear once per device its members were assigned to."""
    spec: object                  # shape-defining representative's spec
    idx: List[int]                # member indices into self.members
    leads: List[int]              # per stacked member, the lead it reads
    stacked: Dict                 # the kind's stack() of the weights
    fn: Callable                  # jitted (stacked, win) -> scores [M, P]
    device: object = None         # jax.Device the shard is pinned to
    slot: int = 0                 # placement slot index (0 if unsharded)
    kind: str = "resnext"         # the members' kind
    tokens: int = 0               # sequence positions per window


def _make_member_fn(params: Dict, spec: EcgModelSpec,
                    impl: str) -> Callable:
    return jax.jit(lambda x: jax.nn.softmax(
        ecg_apply(params, x, spec, impl=impl), axis=-1)[:, 1])


@functools.lru_cache(maxsize=None)
def _make_bucket_fn_cached(spec: EcgModelSpec, leads: Tuple[int, ...],
                           impl: str) -> Callable:
    @jax.jit
    def fn(stacked: Dict, win: jax.Array) -> jax.Array:
        # on-device lead-gather: the shared [Ppad, C, L] window pack is
        # expanded to the stacked [M, Ppad, L, 1] bucket view INSIDE
        # the dispatch — the member axis never exists host-side, so the
        # pack crosses to the device once per flush, not once per member
        xs = jnp.transpose(win[:, leads, :], (1, 0, 2))[..., None]
        logits = ecg_apply_stacked(stacked, xs, spec, impl=impl)
        return jax.nn.softmax(logits, axis=-1)[..., 1]     # [M, P]
    return fn


@functools.lru_cache(maxsize=None)
def _make_bucket_fn_legacy_cached(spec: EcgModelSpec,
                                  impl: str) -> Callable:
    """Pre-refactor dispatch: takes the host-marshaled [M, Ppad, L, 1]
    member-expanded input (``marshal="legacy"``) — kept as the ingest
    microbench baseline and equivalence oracle."""
    if not isinstance(spec, EcgModelSpec):
        raise ValueError(f"the legacy marshal runs ResNeXt programs only, "
                         f"not {type(spec).__name__}")

    @jax.jit
    def fn(stacked: Dict, xs: jax.Array) -> jax.Array:
        logits = ecg_apply_stacked(stacked, xs, spec, impl=impl)
        return jax.nn.softmax(logits, axis=-1)[..., 1]     # [M, P]
    return fn


def _make_bucket_fn(spec: EcgModelSpec, leads: Sequence[int],
                    impl: str, marshal: str = "packed") -> Callable:
    """Shared per (architecture, leads, impl): every service (and every
    staged (selector, placement) pair) reuses ONE jit object per bucket
    shape, so re-staging across swaps/placements hits the compile cache
    instead of recompiling identical programs.  ``name``/``lead`` are
    blanked from the cache key; the packed form instead carries the
    bucket's full lead TUPLE statically — the on-device gather is baked
    into the program, and two buckets whose representative members
    differ only by name share it."""
    blank = dataclasses.replace(spec, name="", lead=0)
    if marshal == "legacy":
        return _make_bucket_fn_legacy_cached(blank, impl)
    return _make_bucket_fn_cached(blank, tuple(leads), impl)


@functools.lru_cache(maxsize=None)
def _make_hybrid_fn(spec: GraniteHybridSpec, ssd_impl: str) -> Callable:
    """One jit object per hybrid shape, named ``hybrid_member`` so the
    device trace shows it as ``jit_hybrid_member``, apart from the zoo's
    ``jit_fn``."""
    def hybrid_member(stacked, win: jax.Array) -> jax.Array:
        logits = granite_apply_stacked(stacked, win, spec,
                                       ssd_impl=ssd_impl)
        return jax.nn.softmax(logits, axis=-1)[..., 1]     # [M, P]
    return jax.jit(hybrid_member)


# flush-size ladder: micro-batches pad up to aggregator.pow2_rung so
# every path (packed / refs / legacy) and the ingest side share one
# log2-bounded set of compiled shapes
_next_pow2 = pow2_rung

# representative flush rung for placement-planning cost measurement:
# serving flushes pad to the pow2 ladder (top default warmup rung 8),
# and per-bucket cost RATIOS at batch 1 differ from ratios at flush
# size (fixed dispatch overhead dominates small stacked calls), so
# planning from batch-1 timings skews the LPT plan
PLAN_BATCH = 8

# EWMA weight for per-shard retire-time tracking (O(1) state per
# (bucket, device) shard; higher = drift shows faster, noisier)
RETIRE_ALPHA = 0.3


@functools.lru_cache(maxsize=None)
def _warmup_pack(L: int, p: int, channels: int = ECG_LEADS
                 ) -> np.ndarray:
    """Shared zero window packs for warmup/staging: every bucket (and
    every service being staged for a hot swap) warms the same
    (length, flush-size) buffer instead of re-materializing windows
    per staged selector."""
    return np.zeros((p, channels, L), np.float32)


class EnsembleService:
    """Stateless ensemble actors with a bucketed fused dispatch plan.

    ``fused=True`` (default): one stacked jitted call per architecture
    bucket per flush, micro-batched across patients.  ``fused=False``:
    the original one-call-per-member-per-patient loop (kept as the
    numerical oracle).  ``dispatch_count`` tallies jitted zoo dispatches
    issued by ``predict``/``predict_batch`` — the quantity the serving
    benchmark tracks per query.

    ``placement`` (a ``serving.placement.Placement`` whose assignment
    covers every member exactly once) shards the fused plan across
    ``devices`` (default ``jax.devices()``): slot d's members are
    bucketed on their own and pinned to device d, one stacked dispatch
    per (bucket, device) shard.  BUCKET-ALIGNED plans (each bucket
    whole on one device — what ``plan_placement`` emits) are bitwise
    identical to the unsharded path: the stacked grouping never
    changes, only where it runs.  Arbitrary member-level assignments
    are also valid but alter the stacked member-axis size, so they
    match to float tolerance only.
    """

    def __init__(self, members: Sequence[ZooMember],
                 vitals_model=None, labs_model=None,
                 n_devices: int = 1, fused: bool = True,
                 impl: str = "xla",
                 placement: Optional[Placement] = None,
                 devices: Optional[Sequence] = None,
                 marshal: str = "packed"):
        self.members = list(members)
        self.vitals_model = vitals_model
        self.labs_model = labs_model
        self.fused = fused
        self.impl = impl
        self.n_devices = n_devices
        self.placement = placement
        if marshal not in ("packed", "legacy"):
            raise ValueError(f"unknown marshal mode {marshal!r}")
        self.marshal = marshal
        self._devices = list(devices) if devices is not None else None
        if placement is not None:
            if not fused:
                raise ValueError("placement requires the fused path")
            placed = sorted(i for slot in placement.assignment
                            for i in slot)
            if placed != list(range(len(self.members))):
                raise ValueError(
                    f"placement must cover every member exactly once: "
                    f"got {placed} for {len(self.members)} members")
        self.dispatch_count = 0
        # fault-injection seam (control.faults.FaultPlane): when set,
        # called with the bucket's pinned device (None = default) right
        # before each stacked dispatch; raising DeviceLostError here is
        # how a "device died mid-flush" materialises to the serving path
        self.dispatch_guard: Optional[Callable] = None
        # ingest-side accounting for BENCH_serving.json["ingest"]:
        # bytes shipped host->device for flush inputs, and host seconds
        # spent building/transferring them (the marshaling cost)
        self.h2d_bytes = 0
        self.marshal_seconds = 0.0
        # live per-shard retire times: (bucket member tuple) -> EWMA of
        # wall-clock seconds from that shard's dispatch to its retire
        # on the fused flush path.  O(1) state per shard (no lists) —
        # the drift signal HotSwapper.re_place / the controller's
        # finish-time imbalance consume.
        self.retire_alpha = RETIRE_ALPHA
        self._shard_ewma: Dict[Tuple[int, ...], float] = {}
        self._count_lock = threading.Lock()    # server workers share us
        if not fused:
            _resnext_only(self.members, "fused=False")
        self._fns: List[Optional[Callable]] = [
            _make_member_fn(m.params, m.spec, impl)
            if m.kind == "resnext" else None for m in self.members]
        self._bucket_cache: Optional[List[_Bucket]] = None

    @classmethod
    def for_selector(cls, pool: Sequence["ZooMember"],
                     selector: np.ndarray, **kwargs) -> "EnsembleService":
        """Service over the subset of ``pool`` a binary selector picks —
        the control plane's staging constructor (swap.HotSwapper)."""
        idx = np.flatnonzero(np.asarray(selector, bool))
        return cls([pool[i] for i in idx], **kwargs)

    # ------------------------------------------------------------ plan
    @property
    def _buckets(self) -> List[_Bucket]:
        """Stacked dispatch plan, built lazily on first fused flush (so
        measurement-only services never pay the param stacking)."""
        if self._bucket_cache is None:
            with self._count_lock:
                if self._bucket_cache is None:
                    self._bucket_cache = self._build_buckets()
        return self._bucket_cache

    def _build_buckets(self) -> List[_Bucket]:
        members = self.members
        if self.placement is None:
            groups = [(0, None, list(range(len(members))))]
        else:
            devs = self._devices if self._devices is not None \
                else jax.devices()
            used = [d for d, slot
                    in enumerate(self.placement.assignment) if slot]
            if used and used[-1] >= len(devs):
                # refuse to silently fold slots onto fewer devices: the
                # plan's makespan/imbalance would describe parallelism
                # that does not exist, poisoning the controller's T_s
                raise ValueError(
                    f"placement uses slot {used[-1]} but only "
                    f"{len(devs)} device(s) are available")
            groups = [(d, devs[d], list(slot))
                      for d, slot in enumerate(self.placement.assignment)
                      if slot]
        out = []
        for slot_idx, dev, mem_idx in groups:
            local_members = [members[i] for i in mem_idx]
            for local in bucket_members(local_members).values():
                idx = [mem_idx[j] for j in local]
                group = [members[i] for i in idx]
                rep = group[0]
                stacked = rep.stack([m.params for m in group])
                if dev is not None:
                    stacked = jax.device_put(stacked, dev)
                out.append(_Bucket(
                    spec=rep.spec, idx=idx,
                    leads=[getattr(m.spec, "lead", None) for m in group],
                    stacked=stacked,
                    fn=rep.bucket_program(group, self.impl, self.marshal),
                    device=dev, slot=slot_idx, kind=rep.kind,
                    tokens=rep.tokens))
        return out

    @property
    def n_buckets(self) -> int:
        """Stacked dispatches per flush: architecture buckets, or
        (bucket, device) shards when a placement is active."""
        return len(self._buckets)

    def plan_placement(self, n_devices: int,
                       bucket_costs: Optional[Sequence[float]] = None,
                       reps: int = 3,
                       batch: Optional[int] = None,
                       speeds: Optional[Sequence[float]] = None
                       ) -> Placement:
        """LPT plan over measured (or given) per-bucket costs, at BUCKET
        granularity: a stacked bucket is atomic, so the plan never splits
        one stacked dispatch across devices.  The returned assignment is
        in member indices, ready for ``EnsembleService(placement=...)``.

        Costs are measured at a REPRESENTATIVE FLUSH RUNG (``batch``,
        default ``PLAN_BATCH``): serving pads flushes to the pow2
        ladder, and per-bucket cost ratios at batch 1 differ from the
        ratios the plan will actually see.  ``speeds`` (one per slot)
        makes the plan heterogeneity-aware — see ``lpt_placement``."""
        groups = list(bucket_members(self.members).values())
        if bucket_costs is None:
            if self.placement is not None:
                raise ValueError("measure bucket costs on an unsharded "
                                 "service (or pass bucket_costs)")
            bucket_costs = self.measured_bucket_costs(
                reps=reps, batch=PLAN_BATCH if batch is None else batch)
        return grouped_lpt_placement(groups, list(bucket_costs),
                                     n_devices, speeds=speeds)

    def analytic_bucket_costs(self) -> List[float]:
        """Per-bucket LPT costs from the members' own ``cost()`` (MACs
        per window), ordered like ``plan_placement``'s groups: a plan
        that needs no timing pass."""
        return [sum(self.members[i].cost() for i in g)
                for g in bucket_members(self.members).values()]

    # ---------------------------------------------------------- warmup
    def _bucket_input(self, b: _Bucket, p: int) -> jax.Array:
        if self.marshal == "legacy":
            x = np.zeros((len(b.idx), p, b.spec.input_len, 1),
                         np.float32)
        else:
            x = _warmup_pack(b.spec.input_len, p)
        if b.device is not None:
            return jax.device_put(x, b.device)
        return jnp.asarray(x)

    def warmup(self, batch_sizes: Sequence[int] = (1, 2, 4, 8)) -> None:
        """Compile every bucket dispatch at the pow2 flush-size ladder
        (the sizes ``predict_batch`` pads to), so the first full-census
        flush after build/staging never pays XLA compile on the
        latency-critical path.  Packed mode shares one zero window pack
        per (input length, flush size, device) across all buckets."""
        if self.fused:
            shared: Dict = {}
            for b in self._buckets:
                for p in batch_sizes:
                    key = (b.spec.input_len, b.device, p)
                    x = shared.get(key)
                    if x is None or self.marshal == "legacy":
                        x = self._bucket_input(b, p)
                        shared[key] = x
                    b.fn(b.stacked, x).block_until_ready()
        else:
            for m, fn in zip(self.members, self._fns):
                fn(jnp.zeros((1, m.spec.input_len, 1)))

    def measured_costs(self, reps: int = 3,
                       warmup: int = 1) -> List[float]:
        """Closed-loop per-member seconds/query (the mu measurement).
        Always uses the per-member fns — the composer's latency profiler
        needs individual member costs regardless of fused serving.
        ``warmup`` untimed calls precede the timed reps so compile time
        never leaks into the estimate."""
        _resnext_only(self.members, "measured_costs")
        out = []
        for m, fn in zip(self.members, self._fns):
            x = jnp.zeros((1, m.spec.input_len, 1))
            for _ in range(max(1, warmup)):
                fn(x).block_until_ready()
            t0 = time.perf_counter()
            for _ in range(reps):
                fn(x).block_until_ready()
            out.append((time.perf_counter() - t0) / reps)
        return out

    def measured_bucket_costs(self, reps: int = 3, batch: int = 1,
                              warmup: int = 1) -> List[float]:
        """Closed-loop seconds per stacked bucket dispatch — the cost
        vector the LPT placement planner consumes.  Each bucket is
        warmed with ``warmup`` untimed calls first: without that, the
        first call's compile time would fold into the cost estimate and
        skew the plan toward whichever bucket compiled first."""
        out = []
        for b in self._buckets:
            x = self._bucket_input(b, batch)
            for _ in range(max(1, warmup)):
                b.fn(b.stacked, x).block_until_ready()
            t0 = time.perf_counter()
            for _ in range(reps):
                b.fn(b.stacked, x).block_until_ready()
            out.append((time.perf_counter() - t0) / reps)
        return out

    # --------------------------------------------------------- serving
    def predict(self, windows) -> float:
        """windows: {"ecg": [3, L], "vitals": [7, W], "labs": [8]} or a
        ``DeviceWindowRef``.  Returns the bagged P(stable) (Eq. 5)."""
        return self.predict_batch([windows])[0]

    def predict_batch(self, batch) -> List[float]:
        """Micro-batched form of ``predict``: one flush for windows
        from len(batch) patients — host window dicts or
        ``DeviceWindowRef``s (never mixed).  Fused packed path: ONE
        [Ppad, 3, L] window pack per distinct input length, shipped at
        most once per device, lead-expanded to the stacked bucket view
        inside each bucket's dispatch; all device work is retired with
        a single blocking gather at the end.  ECG windows shorter than
        a member's input_len are left-zero-padded (the aggregator's
        zero-fill convention), keeping compile shapes static."""
        if not len(batch):
            return []
        if isinstance(batch[0], DeviceWindowRef):
            return self._predict_refs(batch)
        if not self.fused:
            return [self._predict_one_unfused(w) for w in batch]
        if self.marshal == "legacy":
            return self._predict_batch_legacy(batch)

        P = len(batch)
        # pad the micro-batch to the next power of two: per-window
        # forward passes are batch-independent, so zero rows are inert,
        # and flushes of any size hit one of log2(max_batch) compiled
        # programs instead of recompiling per distinct size
        Ppad = _next_pow2(P)
        t_marshal = time.perf_counter()
        packs: Dict[int, np.ndarray] = {}
        for L in sorted({b.spec.input_len for b in self._buckets}):
            win = np.zeros((Ppad, ECG_LEADS, L), np.float32)
            for p, w in enumerate(batch):
                clip = np.asarray(w["ecg"], np.float32)[:, -L:]
                win[p, :, L - clip.shape[-1]:] = clip
            packs[L] = win
        dev_wins, h2d = self._ship_packs(packs)
        marshal_s = time.perf_counter() - t_marshal
        _spans.note("marshal", marshal_s)
        scores = self._flush(dev_wins, P)
        with self._count_lock:
            self.h2d_bytes += h2d
            self.marshal_seconds += marshal_s
        return self._combine(scores, batch)

    def _ship_packs(self, packs: Dict[int, np.ndarray]
                    ) -> Tuple[Dict, int]:
        """Transfer each window pack AT MOST once per device hosting a
        bucket shard; every shard on that device reads the same pinned
        copy.  Returns ({(L, device): array}, bytes shipped)."""
        dev_wins: Dict = {}
        h2d = 0
        for b in self._buckets:
            key = (b.spec.input_len, b.device)
            if key in dev_wins:
                continue
            win = packs[b.spec.input_len]
            nbytes = win.nbytes if isinstance(win, np.ndarray) else 0
            dev_wins[key] = jax.device_put(win, b.device) \
                if b.device is not None else jnp.asarray(win)
            h2d += nbytes
        return dev_wins, h2d

    def _flush(self, dev_wins: Dict, P: int) -> np.ndarray:
        """Issue one stacked dispatch per bucket shard against the
        shipped packs (async), then retire everything with a single
        cross-device gather."""
        score_mat = np.zeros((len(self.members), P))
        pending = []
        guard = self.dispatch_guard
        t_dispatch = time.perf_counter()
        for b in self._buckets:
            # per-shard clock starts BEFORE the guard: an injected
            # per-device stall (faults seam) is device time and must
            # drift that shard's retire EWMA
            t_b = time.perf_counter()
            if guard is not None:
                guard(b.device)
            y = b.fn(b.stacked, dev_wins[(b.spec.input_len, b.device)])
            pending.append((b, y, t_b))                # async dispatch
        with self._count_lock:
            self.dispatch_count += len(pending)
        t_gather = time.perf_counter()
        _spans.note("dispatch", t_gather - t_dispatch)
        for b, y, t_b in pending: # one sync point: cross-device gather
            score_mat[b.idx] = np.asarray(
                jax.block_until_ready(y))[:, :P]
            self._record_retire(b, time.perf_counter() - t_b)
        _spans.note("gather", time.perf_counter() - t_gather)
        return score_mat

    # ------------------------------------------- live shard cost drift
    def _record_retire(self, b: _Bucket, dt: float) -> None:
        """Fold one shard's dispatch->retire wall-clock into its EWMA.
        Attribution is gather-order conservative: shards retired behind
        a slower same-flush shard inherit some of its wait, but a
        persistently slow DEVICE inflates its own shards' EWMAs on
        every flush, so the drift signal converges over repeated
        flushes."""
        key = tuple(sorted(b.idx))
        with self._count_lock:
            prev = self._shard_ewma.get(key)
            self._shard_ewma[key] = dt if prev is None else (
                self.retire_alpha * dt
                + (1.0 - self.retire_alpha) * prev)

    def shard_cost_snapshot(self) -> Dict[Tuple[int, ...], float]:
        """Live per-shard retire EWMAs, keyed by the shard's sorted
        member-index tuple (stable across re-placements for
        bucket-aligned plans).  Empty until the first fused flush."""
        with self._count_lock:
            return dict(self._shard_ewma)

    def live_bucket_costs(self) -> Optional[List[float]]:
        """Measured per-architecture-bucket costs in DEVICE-INDEPENDENT
        work units (retire EWMA x the speed of the slot the bucket
        currently runs on), ordered like ``plan_placement``'s groups —
        i.e. a drop-in ``bucket_costs`` vector for re-planning from
        drift instead of a fresh offline measurement pass.  None until
        every bucket has been observed, or when the active plan is not
        bucket-aligned (member-split shards don't map back to
        architecture buckets)."""
        snap = self.shard_cost_snapshot()
        if not snap:
            return None
        groups = list(bucket_members(self.members).values())
        speed_of = {}
        if self._bucket_cache is not None:
            sp = self.placement.speeds if self.placement is not None \
                else None
            for b in self._bucket_cache:
                speed_of[tuple(sorted(b.idx))] = (
                    sp[b.slot] if sp is not None else 1.0)
        out = []
        for g in groups:
            key = tuple(sorted(g))
            dt = snap.get(key)
            if dt is None:
                return None
            out.append(dt * speed_of.get(key, 1.0))
        return out

    def measured_finish_times(self) -> Optional[List[float]]:
        """Live per-slot finish times (device wall-clock seconds): the
        max retire EWMA over the shards pinned to each slot — the
        last shard to retire IS the device's finish.  None until every
        shard has been observed.  Idle slots report 0.0, so the
        finish-time imbalance over this vector catches stranded
        devices."""
        if self._bucket_cache is None:
            return None
        snap = self.shard_cost_snapshot()
        n_slots = self.placement.n_slots if self.placement is not None \
            else 1
        fin = [0.0] * n_slots
        for b in self._bucket_cache:
            dt = snap.get(tuple(sorted(b.idx)))
            if dt is None:
                return None
            fin[b.slot] = max(fin[b.slot], dt)
        return fin

    def _predict_refs(self, batch: Sequence[DeviceWindowRef]
                      ) -> List[float]:
        """Device-resident flush: the batch's windows already live in a
        ``DeviceIngest`` ring, so the pack is GATHERED on device
        (``gather_windows`` fuses ring unwrap + zero-fill + batch
        padding) and only the flushed (patient, end, valid) int32
        triples cross the host boundary — zero sample bytes of H2D.
        Sharded plans copy the gathered pack device-to-device once per
        shard device.  Bitwise-identical to the host-dict path fed the
        same windows."""
        if not self.fused:
            return [self._predict_one_unfused(self._ref_windows(r))
                    for r in batch]
        if self.marshal == "legacy":
            raise ValueError("DeviceWindowRef flushes need the packed "
                             "marshal (legacy expects member-expanded "
                             "host inputs)")
        ingest = batch[0].ingest
        if any(r.ingest is not ingest for r in batch):
            raise ValueError("a flush must come from one DeviceIngest")
        state = ingest.states["ecg"]
        cap = state.buf.shape[-1]
        P = len(batch)
        Ppad = _next_pow2(P)
        t_marshal = time.perf_counter()
        lens = sorted({b.spec.input_len for b in self._buckets})
        # staleness guard: a ref enqueued behind a long stall can be
        # OUTLIVED by the ring — newer samples overwrite its window.
        # The oldest position any gather will read-and-use is
        # end - min(valid, max L); if ingest has advanced more than cap
        # past it, serving would silently score the WRONG window's
        # data, so refuse instead (the server's safe-batch wrapper
        # turns that into a NaN score for the stale query only).  Two
        # host integers per ref — nothing touches the device.
        l_max = max(lens, default=0)
        for r in batch:
            oldest = r.ends["ecg"] - min(r.valid["ecg"], l_max)
            if int(ingest.fed["ecg"][r.patient]) - oldest > cap:
                raise ValueError(
                    f"stale DeviceWindowRef for patient {r.patient}: "
                    f"the ring (capacity {cap}) has overwritten its "
                    f"window; flush sooner or raise capacity_windows")
        patients = np.zeros(Ppad, np.int32)
        ends = np.zeros(Ppad, np.int32)
        valid = np.zeros(Ppad, np.int32)
        for p, r in enumerate(batch):
            patients[p] = r.patient
            ends[p] = r.ends["ecg"] % cap
            valid[p] = r.valid["ecg"]
        pj, ej, vj = (jnp.asarray(patients), jnp.asarray(ends),
                      jnp.asarray(valid))
        h2d = patients.nbytes + ends.nbytes + valid.nbytes
        packs: Dict[int, jax.Array] = {}
        for L in lens:
            packs[L] = gather_windows(state.buf, pj, ej, vj, L)
        dev_wins, _ = self._ship_packs(packs)   # D2D for remote shards
        marshal_s = time.perf_counter() - t_marshal
        _spans.note("marshal", marshal_s)
        scores = self._flush(dev_wins, P)
        with self._count_lock:
            self.h2d_bytes += h2d
            self.marshal_seconds += marshal_s
        return self._combine(scores, self._refs_side_batch(batch))

    def _refs_side_batch(self, batch: Sequence[DeviceWindowRef]):
        """CPU-side model inputs for a ref flush: with a vitals model
        attached, read ALL flushed patients' vitals windows back in ONE
        batched gather (low-rate, tiny; index arrays padded to the same
        pow2 rung as the ECG path, so flush-size churn never recompiles
        it) instead of one device round-trip per patient, and hand
        ``_combine`` plain dicts.  Without CPU-side models the refs
        pass through untouched and nothing is ever read back."""
        if self.vitals_model is None \
                or "vitals" not in batch[0].ingest.modalities:
            return batch
        ingest = batch[0].ingest
        st = ingest.states["vitals"]
        cap = st.buf.shape[-1]
        want = ingest.want["vitals"]
        # the low-rate ring needs its own staleness guard: its (small)
        # capacity is overrun on a different clock than the ECG ring's
        for r in batch:
            oldest = r.ends["vitals"] - min(r.valid["vitals"], want)
            if int(ingest.fed["vitals"][r.patient]) - oldest > cap:
                raise ValueError(
                    f"stale DeviceWindowRef for patient {r.patient}: "
                    f"the vitals ring (capacity {cap}) has overwritten"
                    f" its window; flush sooner or raise "
                    f"capacity_windows")
        Ppad = _next_pow2(len(batch))
        patients = np.zeros(Ppad, np.int32)
        ends = np.zeros(Ppad, np.int32)
        valid = np.zeros(Ppad, np.int32)
        for p, r in enumerate(batch):
            patients[p] = r.patient
            ends[p] = r.ends["vitals"] % cap
            valid[p] = r.valid["vitals"]
        win = np.asarray(gather_windows(
            st.buf, jnp.asarray(patients), jnp.asarray(ends),
            jnp.asarray(valid), want))
        return [{**r.extra, "vitals": win[p]}
                for p, r in enumerate(batch)]

    def _ref_windows(self, r: DeviceWindowRef) -> Dict[str, np.ndarray]:
        """Materialize a ref as the oracle's host window dict (unfused
        fallback only — the fused path never reads samples back)."""
        out = dict(r.extra)
        for name in r.ends:
            out[name] = r.host_window(name)
        return out

    def _predict_batch_legacy(self, batch) -> List[float]:
        """Pre-refactor hot path: per bucket an [M, Ppad, L, 1] input
        is marshaled by a host (member, patient) double loop and
        shipped whole — M x L floats per patient per bucket.  Kept
        behind ``marshal="legacy"`` as the ingest bench baseline."""
        _resnext_only(self.members, 'marshal="legacy"')
        P = len(batch)
        Ppad = _next_pow2(P)
        score_mat = np.zeros((len(self.members), P))
        pending = []
        h2d = 0
        t_marshal = time.perf_counter()
        guard = self.dispatch_guard
        for b in self._buckets:
            if guard is not None:
                guard(b.device)
            L = b.spec.input_len
            xs = np.zeros((len(b.idx), Ppad, L, 1), np.float32)
            for j, lead in enumerate(b.leads):
                for p, w in enumerate(batch):
                    clip = np.asarray(w["ecg"])[lead, -L:]
                    xs[j, p, L - clip.shape[-1]:, 0] = clip
            h2d += xs.nbytes
            # sharded plan: pin the input beside its pinned params so
            # the dispatch runs on (and stays on) the shard's device
            x = jax.device_put(xs, b.device) if b.device is not None \
                else jnp.asarray(xs)
            y = b.fn(b.stacked, x)
            pending.append((b, y))                     # async dispatch
        marshal_s = time.perf_counter() - t_marshal
        # legacy interleaves marshal + dispatch per bucket; attribute
        # the whole pre-gather segment to marshal
        _spans.note("marshal", marshal_s)
        with self._count_lock:
            self.dispatch_count += len(pending)
            self.h2d_bytes += h2d
            self.marshal_seconds += marshal_s
        t_gather = time.perf_counter()
        for b, y in pending:      # one sync point: cross-device gather
            score_mat[b.idx] = np.asarray(
                jax.block_until_ready(y))[:, :P]
        _spans.note("gather", time.perf_counter() - t_gather)
        return self._combine(score_mat, batch)

    def _predict_one_unfused(self, windows: Dict[str, np.ndarray]
                             ) -> float:
        _resnext_only(self.members, "the unfused per-member path")
        ecg = windows.get("ecg")
        if self.dispatch_guard is not None:
            self.dispatch_guard(None)       # unfused runs on the default
        score_mat = np.zeros((len(self.members), 1))
        for i, (m, fn) in enumerate(zip(self.members, self._fns)):
            L = m.spec.input_len
            clip = np.asarray(ecg)[m.spec.lead, -L:]
            if clip.shape[-1] < L:     # zero-fill short windows (matches
                clip = np.pad(clip, (L - clip.shape[-1], 0))  # aggregator)
            score_mat[i, 0] = float(fn(jnp.asarray(clip)[None, :, None])[0])
        with self._count_lock:
            self.dispatch_count += len(self.members)
        return self._combine(score_mat, [windows])[0]

    def _side_input(self, item, name: str) -> Optional[np.ndarray]:
        """The CPU-side models' input for one batch item: a window-dict
        key, or — for a ``DeviceWindowRef`` — the labs side channel /
        a lazy readback of the (tiny, low-rate) vitals window.  Only
        read when the matching model is attached, so the fused ECG
        path stays readback-free."""
        if isinstance(item, DeviceWindowRef):
            if name in item.extra:
                return item.extra[name]
            if name in item.ends:
                return item.host_window(name)
            return None
        return item.get(name)

    def _combine(self, score_mat: np.ndarray, batch) -> List[float]:
        """Per-patient Eq. 5 mean over zoo scores + CPU-side models."""
        out = []
        for p, windows in enumerate(batch):
            scores = list(score_mat[:, p]) if len(self.members) else []
            if self.vitals_model is not None:
                vit = self._side_input(windows, "vitals")
                if vit is not None:
                    scores.append(float(self.vitals_model.predict_proba(
                        vit[None])[0]))
            if self.labs_model is not None:
                labs = self._side_input(windows, "labs")
                if labs is not None:
                    scores.append(float(self.labs_model.predict_proba(
                        labs[None])[0]))
            out.append(float(np.mean(scores)) if scores else 0.5)
        return out


class TierRouter:
    """Routes each query through its acuity tier's service (the data-
    plane face of per-tier degradation ladders).

    ``services`` maps tier -> anything with ``predict``/``predict_batch``
    (plain ``EnsembleService``s, or ``SwappableService`` facades when the
    control plane hot-swaps per-tier pairs underneath).  Batches must be
    tier-homogeneous — the tier-keyed batcher upstream
    (``serving.queues.KeyedMicroBatcher``) guarantees that — so one
    flush is always answered by exactly one tier's selector.
    """

    def __init__(self, services: Dict[str, object],
                 default: Optional[str] = None):
        if not services:
            raise ValueError("services must be non-empty")
        self.services = dict(services)
        self.default = default if default is not None \
            else next(iter(self.services))
        if self.default not in self.services:
            raise ValueError(f"default {self.default!r} not in "
                             f"{tuple(self.services)}")

    def service(self, tier: Optional[str] = None):
        return self.services[tier if tier in self.services
                             else self.default]

    def predict(self, windows: Dict[str, np.ndarray],
                tier: Optional[str] = None) -> float:
        return self.service(tier).predict(windows)

    def predict_batch(self, batch: Sequence[Dict[str, np.ndarray]],
                      tier: Optional[str] = None) -> List[float]:
        return self.service(tier).predict_batch(batch)


@dataclasses.dataclass
class ServedQuery:
    patient: int
    t_window: float
    t_done: float
    score: float
    # per-stage service attribution (obs.spans stage keys -> seconds),
    # populated when the pipeline serves under span collection
    stages: Optional[Dict[str, float]] = None

    @property
    def latency(self) -> float:
        return self.t_done - self.t_window


class StreamingPipeline:
    """Stateful aggregators + the ensemble service, driven by a stream.

    ``device_ingest=True`` replaces the per-sample python tuple buffers
    with ``serving.aggregator.DeviceIngest``: 250 Hz chunks are staged
    on the host and committed to device-resident ring buffers in
    batches, and a closed window is served as a
    ``DeviceWindowRef`` — the ensemble's flush gathers the samples on
    device, so the ingest->inference path never marshals waveforms
    through the host.  ``PatientAggregator`` (the default) is kept as
    the semantics oracle; the two paths score bitwise-identically
    under the equivalence suite's aligned-feed contract.

    With ``tier_of`` (patient -> acuity tier) the service must be
    tier-routing (``TierRouter`` / ``control.tiers.TieredEnsemble``):
    each closed window is answered by the patient's CURRENT tier's
    service.

    ``engine="slots"`` (requires ``device_ingest=True``, untiered, a
    plain fused ``EnsembleService``) switches from flush-per-window to
    the continuous slot engine (``serving.slots.SlotEngine``): a
    closed window UPDATES the bed's slot, and every ``tick_seconds``
    of logical stream time one tick rescores all occupied slots —
    records are emitted per (window, covering tick) with the slot's
    oracle-exact score."""

    def __init__(self, service, n_patients: int,
                 window_seconds: float = float(CLIP_SECONDS),
                 tier_of: Optional[Callable[[int], str]] = None,
                 device_ingest: bool = False,
                 capacity_windows: float = 2.0,
                 trace_stages: bool = False,
                 engine: str = "flush",
                 tick_seconds: Optional[float] = None):
        mods = [ModalitySpec("ecg", ECG_HZ, ECG_LEADS),
                ModalitySpec("vitals", VITALS_HZ, 7)]
        if engine not in ("flush", "slots"):
            raise ValueError(f"unknown engine {engine!r}")
        if engine == "slots" and not device_ingest:
            raise ValueError('engine="slots" requires device_ingest='
                             "True (slots ARE the device rings)")
        if engine == "slots" and tier_of is not None:
            raise ValueError('engine="slots" is untiered')
        self.engine = engine
        self.tick_seconds = (tick_seconds if tick_seconds is not None
                             else window_seconds)
        self.slot_engine = None
        self._last_tick_t: Optional[float] = None
        self._pending_close: Dict[int, float] = {}
        self.service = service
        self.tier_of = tier_of
        self.device_ingest: Optional[DeviceIngest] = None
        if device_ingest:
            self.device_ingest = DeviceIngest(
                mods, n_patients, window_seconds,
                capacity_windows=capacity_windows)
            # pre-compile the flush gather for every window length the
            # service can ask for (best effort: facades/routers don't
            # expose members — call warm_gather yourself there), so the
            # first closed window never pays XLA compile at serve time
            members = getattr(service, "members", None)
            if members:
                self.device_ingest.warm_gather(
                    tuple(sorted({m.spec.input_len for m in members})))
            # the CPU-side vitals model's batched readback gathers at
            # the same pow2 rungs over the (differently shaped) vitals
            # ring — warm those too, it costs milliseconds
            self.device_ingest.warm_gather(
                (self.device_ingest.want["vitals"],),
                modality="vitals")
            self.aggs = []
        else:
            self.aggs = [PatientAggregator(mods, window_seconds)
                         for _ in range(n_patients)]
        if engine == "slots":
            from repro.serving.slots import SlotEngine
            self.slot_engine = SlotEngine(service, self.device_ingest)
        self.labs_cache: Dict[int, np.ndarray] = {}
        self.records: List[ServedQuery] = []
        self.trace_stages = trace_stages

    def _close(self, t: float, patient: int):
        """The closed window in whichever representation the ingest
        side keeps: a host window dict, or a DeviceWindowRef."""
        if self.device_ingest is not None:
            extra = {}
            if patient in self.labs_cache:
                extra["labs"] = self.labs_cache[patient]
            return self.device_ingest.close_window(patient, t,
                                                   extra=extra)
        windows = self.aggs[patient].pop_window(t)
        if patient in self.labs_cache:
            windows["labs"] = self.labs_cache[patient]
        return windows

    def feed(self, t: float, patient: int, modality: str,
             samples: np.ndarray) -> Optional[ServedQuery]:
        if modality == "labs":
            self.labs_cache[patient] = np.asarray(samples)
            return None
        if self.device_ingest is not None:
            self.device_ingest.ingest(t, patient, modality, samples)
            if not self.device_ingest.window_ready(patient, t):
                return self._maybe_tick(t, patient) \
                    if self.engine == "slots" else None
        else:
            agg = self.aggs[patient]
            agg.ingest(t, modality, samples)
            if not agg.window_ready(t):
                return None
        windows = self._close(t, patient)
        if self.engine == "slots":
            # the closed window updates the bed's slot; scoring happens
            # at the next tick boundary of LOGICAL stream time, covering
            # every slot that closed a window since the last tick
            self.slot_engine.update(windows)
            self._pending_close[patient] = t
            return self._maybe_tick(t, patient)
        t0 = time.perf_counter()
        stages: Optional[Dict[str, float]] = None
        if self.trace_stages:
            with _spans.collect() as acc:
                if self.tier_of is not None:
                    score = self.service.predict(windows,
                                                 self.tier_of(patient))
                else:
                    score = self.service.predict(windows)
            stages = dict(acc)
        elif self.tier_of is not None:
            score = self.service.predict(windows, self.tier_of(patient))
        else:
            score = self.service.predict(windows)
        wall = time.perf_counter() - t0
        rec = ServedQuery(patient=patient, t_window=t, t_done=t + wall,
                          score=score, stages=stages)
        self.records.append(rec)
        return rec

    def _maybe_tick(self, t: float,
                    patient: Optional[int] = None
                    ) -> Optional[ServedQuery]:
        """Fire a slot tick when a tick interval of logical time has
        passed and windows are pending; emit one ``ServedQuery`` per
        pending closed window the tick covered.  Returns ``patient``'s
        record when this tick scored it."""
        if self._last_tick_t is None:
            self._last_tick_t = t
        if t - self._last_tick_t < self.tick_seconds \
                or not self._pending_close:
            return None
        return self.tick_now(t, patient)

    def tick_now(self, t: float,
                 patient: Optional[int] = None) -> Optional[ServedQuery]:
        """Force a slot tick at logical time ``t`` (drain helper: score
        whatever closed windows are still pending)."""
        eng = self.slot_engine
        if eng is None:
            raise ValueError("tick_now needs engine='slots'")
        t0 = time.perf_counter()
        report = eng.tick()
        wall = time.perf_counter() - t0
        self._last_tick_t = t
        out = None
        for s in map(int, report.scored):
            tw = self._pending_close.pop(s, None)
            if tw is None:
                continue        # rescored slot with no new window
            rec = ServedQuery(patient=s, t_window=tw, t_done=t + wall,
                              score=eng.read(s))
            self.records.append(rec)
            if s == patient:
                out = rec
        return out

    def latencies(self) -> np.ndarray:
        return np.asarray([r.latency for r in self.records])
