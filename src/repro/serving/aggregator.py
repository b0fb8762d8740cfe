"""Stateful data aggregators (§3.4, Fig. 4).

Multi-rate, multi-modal sensory streams are buffered per patient so the
ensemble always sees a synchronized observation window Delta-T across all
sensors.  Two implementations share semantics:

* ``PatientAggregator`` — plain-python actor, kept as the semantics
  ORACLE: the serving equivalence suite checks the device path against
  it, and the discrete-event simulator still drives it directly.
* ``AggState`` ring buffers — pure-functional jnp state (one
  ``[n_patients, channels, capacity]`` buffer per modality) updated by
  compiled steps, the JAX-native analogue of the paper's Ray stateful
  actors.  ``DeviceIngest`` wraps them into the serving pipeline's
  device-resident ingest stage: 250 Hz packets are staged on the host
  and committed to the rings in batches by one program, longer chunks
  go in one at a time on a pow2 size ladder (so the compiled-variant
  count stays bounded under mixed-rate feeds), and a closed
  observation window is handed to
  the ensemble as a ``DeviceWindowRef`` — three host integers per
  modality, NO host-side sample marshaling.  The flush side
  (``EnsembleService.predict_batch``) gathers the referenced windows
  straight out of the ring with ``gather_windows`` (the
  ``kernels.ref.window_gather`` program), so samples ingested on the
  device are never copied back to the host on the serving hot path.
"""
from __future__ import annotations

import dataclasses
import functools
import threading
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref as kref
from repro.obs import spans as _spans


# ------------------------------------------------- actor implementation
@dataclasses.dataclass
class ModalitySpec:
    name: str
    rate_hz: float                 # nominal sampling rate
    channels: int


class PatientAggregator:
    """Buffers per-modality samples; emits aligned windows of Delta-T."""

    def __init__(self, modalities: List[ModalitySpec],
                 window_seconds: float):
        self.modalities = {m.name: m for m in modalities}
        self.window = window_seconds
        self.buffers: Dict[str, List[Tuple[float, np.ndarray]]] = {
            m.name: [] for m in modalities}
        self.window_start: Optional[float] = None

    def ingest(self, t: float, modality: str, samples: np.ndarray) -> None:
        if self.window_start is None:
            self.window_start = t
        self.buffers[modality].append((t, np.asarray(samples)))

    def window_ready(self, now: float) -> bool:
        return (self.window_start is not None
                and now - self.window_start >= self.window)

    def pop_window(self, now: float) -> Dict[str, np.ndarray]:
        """Returns {modality: [channels, n_samples]} for the last window,
        dropping data older than the window (noisy-environment tolerant:
        missing samples are zero-filled to the nominal count)."""
        out = {}
        t0 = now - self.window
        for name, spec in self.modalities.items():
            want = max(1, int(round(spec.rate_hz * self.window)))
            rows = [s for (t, s) in self.buffers[name] if t >= t0]
            if rows:
                arr = np.concatenate([np.atleast_2d(r) for r in rows],
                                     axis=-1)[:, -want:]
            else:
                arr = np.zeros((spec.channels, 0), np.float32)
            if arr.shape[-1] < want:             # sensor fell off: pad
                pad = np.zeros((spec.channels, want - arr.shape[-1]),
                               np.float32)
                arr = np.concatenate([pad, arr], axis=-1)
            out[name] = arr.astype(np.float32)
            self.buffers[name] = [(t, s) for (t, s) in self.buffers[name]
                                  if t >= t0]
        self.window_start = now
        return out


# --------------------------------------------- jit-compatible ring buffer
class AggState(NamedTuple):
    """One modality's device-resident ring buffer for all patients."""
    buf: jax.Array            # [n_patients, channels, capacity]
    write_idx: jax.Array      # [n_patients] int32
    total: jax.Array          # [n_patients] int32  samples ever written


def agg_init(n_patients: int, channels: int, capacity: int) -> AggState:
    return AggState(
        buf=jnp.zeros((n_patients, channels, capacity), jnp.float32),
        write_idx=jnp.zeros((n_patients,), jnp.int32),
        total=jnp.zeros((n_patients,), jnp.int32))


def ring_wrap(cap: int) -> int:
    """Wrap modulus for ``write_idx``: the largest multiple of ``cap``
    not exceeding 2**30.  Ring positions are ``write_idx % cap``, so the
    wrap point MUST be a multiple of ``cap`` — wrapping at a plain
    2**30 silently sheared the ring for any capacity that doesn't
    divide 2**30 (the pre-fix behavior; regression-tested)."""
    return max(1, (1 << 30) // cap) * cap


@jax.jit
def ingest_step(state: AggState, patient: jax.Array,
                samples: jax.Array) -> AggState:
    """Append samples [channels, k] for one patient (ring semantics).
    Retraces per distinct ``k``: the plain one-packet-at-a-time
    reference that the batched ``_ingest_padded`` is checked against."""
    cap = state.buf.shape[-1]
    k = samples.shape[-1]
    idx = (state.write_idx[patient] + jnp.arange(k)) % cap
    buf = state.buf.at[patient, :, idx].set(samples.T)
    return AggState(
        buf=buf,
        write_idx=state.write_idx.at[patient].add(k) % ring_wrap(cap),
        total=state.total.at[patient].add(k))


def pow2_rung(n: int) -> int:
    """Next power of two >= ``n`` (min 1): the ONE static-shape ladder
    shared by ingest chunk padding, flush batch padding and ring
    capacities, so every padded shape in the data plane lands on the
    same log2-bounded set of compiled programs."""
    return 1 << (max(1, int(n)) - 1).bit_length()


def chunk_rung(k: int) -> int:
    """Static chunk-size ladder: incoming chunks are right-zero-padded
    to a ``pow2_rung`` so ``ingest_chunk`` compiles at most
    ``log2(max_chunk)`` variants under mixed-rate feeds instead of one
    per distinct chunk length."""
    return pow2_rung(k)


#: chunks of at most this many samples (0.5 s of 250 Hz ECG) are staged
#: on the host and reach the ring in batches; longer ones go straight in
PACKET_RUNG = 128
#: staged packets per ring per commit: the batch ``_ingest_padded`` runs at
STAGE_ROWS = 64


@jax.jit
def _ingest_padded(state: AggState, samples: jax.Array,
                   patient: jax.Array, start: jax.Array,
                   n_valid: jax.Array) -> AggState:
    """Batched ring update: row ``b`` of ``samples`` [B, channels, rung]
    holds ``n_valid[b]`` real samples for ``patient[b]``, written from
    ring position ``start[b]`` on.  Pad lanes and pad rows
    (``n_valid == 0``) scatter to the out-of-bounds position ``cap`` and
    are dropped, so the ring never sees the padding.  ``write_idx`` and
    ``total`` advance by scatter-add over ``patient``, so a patient's
    rows add up; no patient may get more than ``cap`` samples in one
    call, or their positions would collide."""
    cap = state.buf.shape[-1]
    lane = jnp.arange(samples.shape[-1])
    pos = (start[:, None] + lane) % cap
    pos = jnp.where(lane < n_valid[:, None], pos, cap)  # OOB -> dropped
    buf = state.buf.at[patient[:, None], :, pos].set(
        jnp.swapaxes(samples, 1, 2), mode="drop")
    return AggState(
        buf=buf,
        write_idx=state.write_idx.at[patient].add(n_valid)
        % ring_wrap(cap),
        total=state.total.at[patient].add(n_valid))


def ingest_chunk(state: AggState, patient: int, samples: np.ndarray,
                 start: Optional[int] = None) -> AggState:
    """Append one variable-length chunk as a batch of one, right-padded
    to its pow2 rung: one compiled variant per rung, not per chunk
    length.  ``start`` is the ring position of its first sample
    (``write_idx[patient] % cap``, read back from the device when not
    given)."""
    samples = np.atleast_2d(np.asarray(samples, np.float32))
    c, k = samples.shape
    cap = state.buf.shape[-1]
    if k > cap:
        raise ValueError(f"chunk of {k} samples exceeds ring capacity "
                         f"{cap}")
    if start is None:
        start = int(state.write_idx[patient]) % cap
    row = np.zeros((1, c, chunk_rung(k)), np.float32)
    row[0, :, :k] = samples
    return _ingest_padded(state, row, np.array([patient], np.int32),
                          np.array([start], np.int32),
                          np.array([k], np.int32))


@functools.partial(jax.jit, static_argnums=(2,))
def read_window(state: AggState, patient: jax.Array,
                want: int) -> jax.Array:
    """Last ``want`` samples, oldest first: [channels, want]."""
    cap = state.buf.shape[-1]
    end = state.write_idx[patient]
    idx = (end - want + jnp.arange(want)) % cap
    return state.buf[patient, :, idx].T


def read_window_static(state: AggState, patient: int, want: int
                       ) -> jax.Array:
    return read_window(state, jnp.asarray(patient), want)


@functools.partial(jax.jit, static_argnums=(4,))
def gather_windows(buf: jax.Array, patients: jax.Array,
                   ends: jax.Array, valid: jax.Array,
                   want: int) -> jax.Array:
    """One-dispatch flush gather: the last ``want`` samples for each
    flushed patient, ``[P, channels, want]`` oldest-first, with
    left-zero-fill fused in (``valid[i] < want`` rows) and pow2 batch
    padding (``valid == 0`` rows all-zero).  ``ends`` are sample
    counts at window close (any integers — reduced mod capacity), so a
    ref stays readable even while newer samples keep streaming into the
    ring, as long as fewer than ``cap - want`` arrive before the flush.
    Pure data movement: bitwise-identical to the host-marshaled pack.
    """
    return kref.window_gather(buf, patients, ends, valid, want)


# ----------------------------------------- device-resident ingest stage
class DeviceWindowRef(NamedTuple):
    """A closed observation window that LIVES in a ``DeviceIngest``
    ring: per modality just ``(end, valid)`` sample counts — the flush
    gathers the samples on device, so handing a window to the server
    costs a few host integers instead of a [channels, want] copy.
    ``extra`` carries host-side side-channel inputs (labs vector)."""
    ingest: "DeviceIngest"
    patient: int
    ends: Dict[str, int]
    valid: Dict[str, int]
    extra: Dict[str, np.ndarray]

    def host_window(self, modality: str) -> np.ndarray:
        """Read this window back as the oracle's [channels, want] array
        (CPU-side models / debugging; NOT the serving hot path).
        Staleness-guarded like the fused flush: a ref whose ring slot
        has been overwritten by later ingest raises instead of silently
        returning the newer window's samples."""
        di = self.ingest
        st = di.states[modality]
        cap = st.buf.shape[-1]
        want = di.want[modality]
        oldest = self.ends[modality] - min(self.valid[modality], want)
        if int(di.fed[modality][self.patient]) - oldest > cap:
            raise ValueError(
                f"stale DeviceWindowRef for patient {self.patient}: "
                f"the {modality} ring (capacity {cap}) has overwritten"
                f" its window; flush sooner or raise capacity_windows")
        win = gather_windows(
            st.buf, jnp.asarray([self.patient], jnp.int32),
            jnp.asarray([self.ends[modality] % cap], jnp.int32),
            jnp.asarray([self.valid[modality]], jnp.int32),
            want)
        return np.asarray(win[0])


class _Stage:
    """One ring's host staging area: packets wait here, in the row
    layout of ``_ingest_padded``, for the next commit."""

    __slots__ = ("shape", "samples", "patient", "start", "n_valid", "n",
                 "per_patient")

    def __init__(self, channels: int, width: int):
        self.shape = (STAGE_ROWS, channels, width)
        self._fresh()

    def _fresh(self) -> None:
        self.samples = np.zeros(self.shape, np.float32)
        self.patient = np.zeros(STAGE_ROWS, np.int32)
        self.start = np.zeros(STAGE_ROWS, np.int32)
        self.n_valid = np.zeros(STAGE_ROWS, np.int32)
        self.n = 0
        self.per_patient: Dict[int, int] = {}

    def fits(self, patient: int, k: int, cap: int) -> bool:
        """Room for a ``k``-sample packet of ``patient``: a free row, and
        the patient's staged samples stay within the ring's capacity."""
        return (self.n < STAGE_ROWS
                and self.per_patient.get(patient, 0) + k <= cap)

    def add(self, patient: int, start: int, samples: np.ndarray) -> None:
        i, k = self.n, samples.shape[-1]
        self.samples[i, :, :k] = samples
        self.patient[i] = patient
        self.start[i] = start
        self.n_valid[i] = k
        self.per_patient[patient] = self.per_patient.get(patient, 0) + k
        self.n = i + 1

    def take(self) -> Tuple[np.ndarray, ...]:
        """Hand the rows over and start on fresh arrays: the program may
        still read the old ones after the call that took them returns."""
        out = (self.samples, self.patient, self.start, self.n_valid)
        self._fresh()
        return out


class DeviceIngest:
    """Device-resident multi-patient ingest: one ``AggState`` ring per
    modality, fed in batches by ``_ingest_padded``.

    Window accounting stays on the host as plain integers (samples fed
    per patient, high-water mark at the last window close); the samples
    themselves never leave the device.  ``close_window`` emits a
    ``DeviceWindowRef`` whose ``valid`` is the number of samples that
    arrived inside the window (clamped to the nominal count), which is
    exactly the ``PatientAggregator`` zero-fill contract: fewer samples
    -> left-zero-fill, more -> keep the last nominal-count many.

    ``capacity_windows`` rings hold that many windows of slack, so a
    ref enqueued behind a busy server stays readable while the next
    window's samples stream in underneath it.

    Staging: an ``ingest`` of at most ``PACKET_RUNG`` samples is host
    work only.  It copies the packet into the modality's stage with the
    ring position it starts at (``fed % cap``, the position
    ``write_idx`` gives, since the wrap is a multiple of ``cap``).  A
    commit writes the whole stage with one upload and one call of
    ``_ingest_padded``.  It runs when someone reads the rings (``states``
    commits first), and in line when a packet finds its stage full or
    would take a patient past ``cap`` samples in one commit.  A longer
    chunk commits the stage, then goes in as a batch of one.

    Concurrency contract: one lock covers the stage append, the stage
    swap and the replacement of ``states[m]``, so commits apply in
    ingest order.  Every update is FUNCTIONAL — ``states[m]`` is
    replaced, never mutated, and the program does not donate the ring
    — so a reader's snapshot of ``states[m]`` (a tick or flush in
    flight) stays valid and immutable while ingest keeps advancing.
    ``fed`` moves under the same lock as the stage, so a ring read
    through ``states`` never holds samples that ``fed`` does not count.
    """

    def __init__(self, modalities: List[ModalitySpec],
                 n_patients: int, window_seconds: float,
                 capacity_windows: float = 2.0):
        self.modalities = {m.name: m for m in modalities}
        self.window = window_seconds
        self.n_patients = n_patients
        self._lock = threading.Lock()
        self._states: Dict[str, AggState] = {}
        self._stage: Dict[str, _Stage] = {}
        self.cap: Dict[str, int] = {}
        self.want: Dict[str, int] = {}
        self.fed: Dict[str, np.ndarray] = {}
        self.mark: Dict[str, np.ndarray] = {}
        self._stats = {"commits": 0, "packets": 0, "full": 0, "direct": 0}
        for m in modalities:
            want = max(1, int(round(m.rate_hz * window_seconds)))
            cap = chunk_rung(max(2, int(np.ceil(
                capacity_windows * want))))          # pow2: wrap-exact
            self._states[m.name] = agg_init(n_patients, m.channels, cap)
            self._stage[m.name] = _Stage(m.channels, min(PACKET_RUNG, cap))
            self.cap[m.name] = cap
            self.want[m.name] = want
            self.fed[m.name] = np.zeros(n_patients, np.int64)
            self.mark[m.name] = np.zeros(n_patients, np.int64)
        self.window_start: List[Optional[float]] = [None] * n_patients
        self._warm_commit()

    @property
    def states(self) -> Dict[str, AggState]:
        """The rings, with every staged packet committed first: the one
        way to read them."""
        with self._lock:
            for name in self._stage:
                self._commit_locked(name)
            return self._states

    def _commit_locked(self, name: str, full: bool = False) -> None:
        stage = self._stage[name]
        if not stage.n:
            return
        with _spans.phase("ingest.commit"):
            n = stage.n
            self._states[name] = _ingest_padded(self._states[name],
                                                *stage.take())
        self._stats["commits"] += 1
        self._stats["packets"] += n
        self._stats["full"] += full

    def _warm_commit(self) -> None:
        """Compile the commit program at each stage's one shape, off the
        serving path (the empty stage leaves the ring as it is)."""
        for name, stage in self._stage.items():
            jax.block_until_ready(_ingest_padded(self._states[name],
                                                 *stage.take()))

    def stats(self) -> Dict[str, float]:
        """Commits of the stage, packets they wrote, commits forced by a
        full stage, chunks written straight in, packets per commit."""
        with self._lock:
            out: Dict[str, float] = dict(self._stats)
        out["packets_per_commit"] = (out["packets"] / out["commits"]
                                     if out["commits"] else 0.0)
        return out

    def grow(self, n_patients: int) -> None:
        """Grow the census to ``n_patients`` ring rows (no-op when
        already large enough).  Each modality's ring is replaced by a
        zero-padded copy along the patient axis — a FUNCTIONAL update,
        so an in-flight flush's snapshot of the old (smaller) state
        stays valid, exactly like a commit's replacement contract.
        Existing rows keep their samples and window accounting bitwise;
        new rows start empty.  Like ``ingest``, growth assumes a single
        feeding thread per modality (the ``SlotEngine`` serializes its
        growth against live ticks separately)."""
        if n_patients <= self.n_patients:
            return
        add = n_patients - self.n_patients
        with self._lock:
            for name in self._stage:
                self._commit_locked(name)
                st = self._states[name]
                self._states[name] = AggState(
                    buf=jnp.pad(st.buf, ((0, add), (0, 0), (0, 0))),
                    write_idx=jnp.pad(st.write_idx, (0, add)),
                    total=jnp.pad(st.total, (0, add)))
                self.fed[name] = np.pad(self.fed[name], (0, add))
                self.mark[name] = np.pad(self.mark[name], (0, add))
            self.window_start.extend([None] * add)
            self.n_patients = n_patients
            self._warm_commit()

    def ingest(self, t: float, patient: int, modality: str,
               samples: np.ndarray) -> None:
        with _spans.phase(f"ingest.{modality}"):
            samples = np.atleast_2d(np.asarray(samples, np.float32))
            k = samples.shape[-1]
            cap = self.cap[modality]
            stage = self._stage[modality]
            with self._lock:
                fed = self.fed[modality]
                start = int(fed[patient] % cap)
                if k <= stage.shape[-1]:
                    if not stage.fits(patient, k, cap):
                        self._commit_locked(modality, full=True)
                    stage.add(patient, start, samples)
                else:
                    self._commit_locked(modality)
                    self._states[modality] = ingest_chunk(
                        self._states[modality], patient, samples, start)
                    self._stats["direct"] += 1
                fed[patient] += k
            if self.window_start[patient] is None:
                self.window_start[patient] = t

    def window_ready(self, patient: int, now: float) -> bool:
        ws = self.window_start[patient]
        return ws is not None and now - ws >= self.window

    def close_window(self, patient: int, now: float,
                     extra: Optional[Dict[str, np.ndarray]] = None
                     ) -> DeviceWindowRef:
        """Close the patient's window: snapshot (end, valid) counts per
        modality, advance the high-water mark, and return the ref.  The
        samples stay put — the flush gathers them on device."""
        ends, valid = {}, {}
        for name in self.modalities:
            end = int(self.fed[name][patient])
            ends[name] = end
            valid[name] = min(end - int(self.mark[name][patient]),
                              self.want[name])
            self.mark[name][patient] = end
        self.window_start[patient] = now
        return DeviceWindowRef(ingest=self, patient=patient, ends=ends,
                               valid=valid, extra=dict(extra or {}))

    def headroom(self, patient: int,
                 modality: Optional[str] = None) -> float:
        """Slack left before a ref closed at the CURRENT mark would be
        overwritten in a ring (conservatively assuming the ref needs a
        full ``want``-sample window).  The ingest side's backpressure
        signal.

        With a ``modality`` name: that ring's headroom in SAMPLES (an
        int), the per-ring view.  With ``modality=None`` (the driver
        default): the MINIMUM across all modalities, normalized to
        WINDOW units (samples of slack / window length) so the
        differently-clocked rings are comparable — a 250 Hz ECG ring
        and a 1 Hz vitals ring overrun on different clocks, and the
        pre-fix ECG-only signal let a vitals-stale ref pass admission
        and NaN downstream.  At ``< 1.0`` (less than one full window of
        slack in SOME ring) further feeding will push outstanding
        windows past a staleness guard, so the driver should reject
        (and count) new queries rather than let them go
        stale-then-NaN."""
        if modality is not None:
            cap = self.cap[modality]
            mark = int(self.mark[modality][patient])
            fed = int(self.fed[modality][patient])
            oldest = max(0, mark - self.want[modality])
            return cap - (fed - oldest)
        return min(self.headroom(patient, m) / self.want[m]
                   for m in self.modalities)

    def headroom_by_modality(self, patient: int) -> Dict[str, float]:
        """Per-ring headroom breakdown in samples (the per-modality
        view behind the min-aggregated backpressure signal)."""
        return {m: self.headroom(patient, m) for m in self.modalities}

    def warm_gather(self, lens: Tuple[int, ...],
                    batch_sizes: Tuple[int, ...] = (1, 2, 4, 8),
                    modality: str = "ecg") -> None:
        """Pre-compile the flush gather at every (window length, pow2
        flush size) the service will hit, off the latency path."""
        st = self.states[modality]
        z = jnp.zeros((max(batch_sizes),), jnp.int32)
        for L in lens:
            for p in batch_sizes:
                jax.block_until_ready(gather_windows(
                    st.buf, z[:p], z[:p], z[:p], L))
