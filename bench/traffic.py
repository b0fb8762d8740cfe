"""Seeded ICU traffic for the benchmark: bedside streams and their
open-loop schedule.

The physiology generators (``sample_patient``, ``ecg_clip``,
``vitals_clip``, ``labs_sample``) are copies of the program's
``training/data.py`` generators, kept here so that a change to the
program cannot move the traffic.  ``ecg_clip`` finds each beat's
samples by ``searchsorted`` instead of a full-length mask per beat; the
arithmetic per sample is unchanged, so the output is bitwise the
original's (``tests/test_bench_traffic.py``).

A traffic mix is a JSON file under ``traffic/`` read by ``load_mix``;
``build_traffic`` turns a mix and a seed into the streams, the labs
vectors and the event schedule that ``harness.py`` replays.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Dict, List, Tuple

import numpy as np

N_VITALS = 7
N_LABS = 8
ECG_LEADS = 3
ECG_HZ = 250
VITALS_HZ = 1
CLIP_SECONDS = 30

HERE = os.path.dirname(os.path.abspath(__file__))


# ------------------------------------------------ copied generators
@dataclasses.dataclass
class PatientParams:
    heart_rate: float          # bpm
    hrv: float                 # beat-to-beat jitter (s)
    noise: float               # additive noise std
    st_offset: float           # ST-segment elevation (class signal)
    vitals_base: np.ndarray    # [N_VITALS]
    vitals_drift: np.ndarray   # [N_VITALS] per-second drift
    labs: np.ndarray           # [N_LABS]


def sample_patient(rng: np.random.Generator, label: int,
                   atypicality: float = 0.0) -> PatientParams:
    """label 0 = critical, 1 = stable; ``atypicality`` blends the
    physiology toward the other class."""
    a = float(np.clip(atypicality, 0.0, 0.9))

    def mix(crit_lo, crit_hi, stab_lo, stab_hi):
        crit_v = rng.uniform(crit_lo, crit_hi)
        stab_v = rng.uniform(stab_lo, stab_hi)
        own, other = (crit_v, stab_v) if label == 0 else (stab_v, crit_v)
        return float((1 - a) * own + a * other)

    crit_bias, stab_bias = 0.8, -0.2
    bias = (1 - a) * (crit_bias if label == 0 else stab_bias) \
        + a * (stab_bias if label == 0 else crit_bias)
    return PatientParams(
        heart_rate=mix(130, 170, 100, 130),
        hrv=mix(0.002, 0.01, 0.02, 0.05),
        noise=mix(0.08, 0.2, 0.02, 0.08),
        st_offset=mix(0.08, 0.25, -0.02, 0.05),
        vitals_base=rng.normal(0.0, 0.5, N_VITALS) + bias,
        vitals_drift=rng.normal(0.0, (1 - a) * 0.02 + a * 0.005
                                if label == 0 else
                                (1 - a) * 0.005 + a * 0.02, N_VITALS),
        labs=rng.normal((1 - a) * (0.45 if label == 0 else -0.25)
                        + a * (-0.25 if label == 0 else 0.45), 0.45,
                        N_LABS),
    )


def _ecg_beat(t: np.ndarray, st: float) -> np.ndarray:
    """Crude PQRST morphology on t in [0, 1)."""
    p = 0.15 * np.exp(-((t - 0.15) / 0.03) ** 2)
    q = -0.2 * np.exp(-((t - 0.35) / 0.012) ** 2)
    r = 1.2 * np.exp(-((t - 0.40) / 0.015) ** 2)
    s = -0.3 * np.exp(-((t - 0.45) / 0.015) ** 2)
    tw = 0.3 * np.exp(-((t - 0.65) / 0.05) ** 2)
    st_seg = st * ((t > 0.45) & (t < 0.62)).astype(float)
    return p + q + r + s + tw + st_seg


_LEAD_GAIN = np.array([1.0, 1.35, 0.75])


def ecg_clip(rng: np.random.Generator, pp: PatientParams,
             seconds: int = CLIP_SECONDS, hz: int = ECG_HZ) -> np.ndarray:
    """[3 leads, seconds*hz] waveform clip."""
    n = seconds * hz
    beat_len = 60.0 / pp.heart_rate
    t = 0.0
    ts = np.arange(n) / hz
    starts = []
    while t < seconds + beat_len:
        starts.append(t)
        t += beat_len + rng.normal(0.0, pp.hrv)
    sig = np.zeros(n)
    for s0, s1 in zip(starts[:-1], starts[1:]):
        lo = int(np.searchsorted(ts, s0, side="left"))
        hi = int(np.searchsorted(ts, s1, side="left"))
        if hi > lo:
            sig[lo:hi] = _ecg_beat((ts[lo:hi] - s0) / max(s1 - s0, 1e-3),
                                   pp.st_offset)
    clips = (sig[None, :] * _LEAD_GAIN[:, None]
             + rng.normal(0.0, pp.noise, (3, n)))
    return clips.astype(np.float32)


def vitals_clip(rng: np.random.Generator, pp: PatientParams,
                seconds: int = CLIP_SECONDS) -> np.ndarray:
    """[N_VITALS, seconds] 1 Hz vitals."""
    t = np.arange(seconds * VITALS_HZ)
    base = pp.vitals_base[:, None] + pp.vitals_drift[:, None] * t[None, :]
    return (base + rng.normal(0, 0.1, base.shape)).astype(np.float32)


def labs_sample(rng: np.random.Generator, pp: PatientParams) -> np.ndarray:
    return (pp.labs + rng.normal(0, 0.2, N_LABS)).astype(np.float32)


# ----------------------------------------------------------- the mix
def load_mix(name: str) -> Dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        mix = json.load(f)
    for key in ("beds", "packet_s", "hop_s", "preroll_s"):
        if key not in mix:
            raise ValueError(f"traffic mix {name!r} lacks {key!r}")
    n_hop = mix["hop_s"] / mix["packet_s"]
    if abs(n_hop - round(n_hop)) > 1e-9 or n_hop < 1:
        raise ValueError(f"traffic mix {name!r}: hop_s must be a whole "
                         f"number of ECG packets")
    return mix


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), *stream])


def _atypicality(rng: np.random.Generator) -> float:
    # make_icu_dataset's draw at its default ambiguity (0.35)
    return float(rng.beta(1.2, 3.0)) * min(1.0, 0.35 * 3)


def side_cohort(seed: int, n: int, seconds: int) -> Dict[str, np.ndarray]:
    """The labelled cohort the side models are fitted on: one vitals
    window of ``seconds`` and one labs vector per patient, classes
    alternating."""
    rng = _rng(seed, 2)
    vit, labs, ys = [], [], []
    for p in range(n):
        pp = sample_patient(rng, p % 2, atypicality=_atypicality(rng))
        vit.append(vitals_clip(rng, pp, seconds))
        labs.append(labs_sample(rng, pp))
        ys.append(p % 2)
    return {"vitals": np.stack(vit), "labs": np.stack(labs),
            "label": np.asarray(ys, np.float64)}


# event kinds, in the order they run when due at the same instant
ECG, VITALS, CLOSE = 0, 1, 2


@dataclasses.dataclass
class Traffic:
    """Everything one run sends, made from the seed before the clock
    starts.  Times are seconds after the live phase begins; the
    measured window is ``[window_start, window_end)``."""
    beds: int
    packet: int                   # ECG samples per packet
    history: int                  # ECG samples in the ring before live
    vitals_history: int
    ecg: np.ndarray               # [beds, 3, n] float32
    vitals: np.ndarray            # [beds, 7, m] float32, or empty
    labs: np.ndarray              # [beds, closes, 8] float32, or empty
    events: np.ndarray            # [E, 4] (time, kind, bed, index)
    window_start: float
    window_end: float
    close_ends: np.ndarray        # [beds, closes] ECG samples at close j
    close_vends: np.ndarray       # [beds, closes] vitals samples at close j


def build_traffic(mix: Dict, seed: int, seconds: float, window_s: int,
                  vitals: bool, labs: bool) -> Traffic:
    """Streams and the open-loop schedule for ``seconds`` of measured
    window, with ``window_s`` (the model's input window) of history.

    Bed ``b`` is offset by ``o_b = hop_s * b / beds``: its ECG packets
    fall at ``o_b + k * packet_s``, and at every ``hop_s / packet_s``-th
    packet, from the first on, right after that packet lands, it closes
    the sliding window of the last ``window_s`` seconds.  The closes of
    the census are thus ``hop_s / beds`` apart from the first instant of
    the live phase on, so the window is steady whatever the pre-roll.
    Close 0 of every bed ends on the history and is sent in set-up.
    Vitals packets (one 1 Hz sample each) fall at ``o_b + k * 1 s``.
    Every seed gets the same schedule; only the signals differ."""
    beds = int(mix["beds"])
    p_s = float(mix["packet_s"])
    hop = float(mix["hop_s"])
    win = int(window_s)
    pre = float(mix["preroll_s"])
    n_hop = int(round(hop / p_s))
    packet = int(round(p_s * ECG_HZ))
    history = win * ECG_HZ
    live = pre + float(seconds)
    n_packets = int(np.ceil(live / p_s)) + 1
    n_closes = n_packets // n_hop + 2
    n_vit = int(np.ceil(live)) + 1
    ecg = np.zeros((beds, ECG_LEADS, history + n_packets * packet),
                   np.float32)
    vit_hist = win * VITALS_HZ
    vit = np.zeros((beds, N_VITALS, vit_hist + n_vit) if vitals
                   else (0, N_VITALS, 0), np.float32)
    lab = np.zeros((beds, n_closes, N_LABS) if labs else (0, 0, N_LABS),
                   np.float32)
    ecg_s = ecg.shape[-1] // ECG_HZ + 1
    for b in range(beds):
        rng = _rng(seed, 1, b)
        pp = sample_patient(rng, b % 2, atypicality=_atypicality(rng))
        ecg[b] = ecg_clip(rng, pp, ecg_s)[:, :ecg.shape[-1]]
        if vitals:
            vit[b] = vitals_clip(rng, pp, vit.shape[-1])
        if labs:
            for j in range(n_closes):
                lab[b, j] = labs_sample(rng, pp)

    ev: List[Tuple[float, int, int, int]] = []
    ends = np.zeros((beds, n_closes), np.int64)
    vends = np.zeros((beds, n_closes), np.int64)
    ends[:, 0] = history          # close 0: the filled history, in set-up
    vends[:, 0] = vit_hist
    for b in range(beds):
        o = hop * b / beds
        for k in range(n_packets):
            t = o + k * p_s
            if t >= live:
                break
            ev.append((t, ECG, b, k))
            if k % n_hop == 0:
                j = k // n_hop + 1
                ends[b, j] = history + (k + 1) * packet
                ev.append((t, CLOSE, b, j))
        if vitals:
            for k in range(n_vit):
                t = o + k * 1.0
                if t >= live:
                    break
                ev.append((t, VITALS, b, k))
    ev.sort(key=lambda e: (e[0], e[1], e[2]))
    events = np.asarray(ev, np.float64)
    if vitals:
        # vitals fed before each close, read off the schedule
        for b in range(beds):
            mine = events[events[:, 2] == b]
            nv = 0
            for t, kind, _, idx in mine:
                if kind == VITALS:
                    nv += 1
                elif kind == CLOSE:
                    vends[b, int(idx)] = vit_hist + nv
    return Traffic(beds=beds, packet=packet, history=history,
                   vitals_history=vit_hist, ecg=ecg, vitals=vit, labs=lab,
                   events=events, window_start=pre,
                   window_end=pre + float(seconds),
                   close_ends=ends, close_vends=vends)
