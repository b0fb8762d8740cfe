"""Bring-up check: serve the paper's full ECG zoo to a 64-bed census on
the chip, through the entry points a deployment calls.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # sharded placement over four chips

One chip (the default):

1. build the paper's full zoo (``zoo_specs(reduced=False)``: 60 members
   in 20 architecture buckets, widths 8-128, 2-16 blocks, 30 s clips at
   250 Hz) with seeded random weights;
2. admit 64 beds into a ``DeviceIngest`` and feed each one 3-lead
   window made by ``training.data.ecg_clip`` from ``--seed``;
3. serve the census through the ``SlotEngine`` (admit, tick, read every
   slot) and through the flush path (``EnsembleService.predict_batch``
   on the same ``DeviceWindowRef``s);
4. check that slot scores equal flush scores bitwise (both run the same
   compiled bucket programs), and that both agree with a plain float32
   reference (``ecg_apply`` per member under
   ``jax.default_matmul_precision("highest")``, then the member mean)
   within ``SCORE_TOL``;
5. run the widest, deepest bucket once more with the Pallas stripe-conv
   kernel compiled for the chip and compare it with the XLA bucket.

``--chips 4`` runs only the sharded path and what it is compared with:
the same census served by the full zoo placed over four chips by
``grouped_lpt_placement`` (ticked and flushed) and by one chip, in this
one process.  It checks that the scores agree, reports whether they are
bitwise equal, and checks that every chip holds the shards placed on it.

Any failed phase raises, so the process exits non-zero and prints no
result line.  The last line of a passing run is the JSON object
``{"ok": true, "device": {"platform", "kind", "count"}}``.  Timings
printed on the way are smoke readings from one short run, not metrics.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

N_BEDS = 64            # the paper's 64-bed unit
# Served scores vs the float32 reference, absolute, on each bed's
# ensemble score.  The chip runs float32 matmuls and convolutions at its
# default precision: one bfloat16 pass, relative rounding 2**-9 per
# operand.  The deepest members stack 49 convolutions (stem + 3 per
# block x 16); GroupNorm rescales between them, so the relative errors
# add at worst linearly: 49 * 2**-9 ~ 0.1 of an O(1) logit.  A two-class
# softmax moves at most 0.25 per unit of logit gap: 0.25 * 0.1 = 0.024.
SCORE_TOL = 2.5e-2
# sharded vs one-chip scores: the same programs on chips of one kind
SHARD_TOL = 1e-6
N_TICKS = 3


class SmokeFailure(RuntimeError):
    """A phase of the smoke run produced a wrong or missing answer."""


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _blank(spec):
    """The shape-defining part of a member spec (name and lead only pick
    the input), so one compiled program serves a whole bucket."""
    return dataclasses.replace(spec, name="", lead=0)


def _build_zoo(reduced: bool, seed: int):
    import jax
    from repro.configs.ecg_zoo import zoo_specs
    from repro.models.ecg_resnext import init_ecg
    from repro.serving.pipeline import ZooMember
    return [ZooMember(s, init_ecg(jax.random.PRNGKey(seed + i), s))
            for i, s in enumerate(zoo_specs(reduced=reduced))]


def _census(members, n_beds: int, seed: int):
    """One closed ECG window per bed, resident in a ``DeviceIngest``."""
    from repro.configs.ecg_zoo import ECG_HZ, ECG_LEADS
    from repro.serving.aggregator import DeviceIngest, ModalitySpec
    from repro.training.data import ecg_clip, sample_patient
    seconds = max(m.spec.input_len for m in members) // ECG_HZ
    rng = np.random.default_rng(seed)
    clips = np.stack([ecg_clip(rng, sample_patient(rng, b % 2), seconds)
                      for b in range(n_beds)])
    ingest = DeviceIngest([ModalitySpec("ecg", ECG_HZ, ECG_LEADS)],
                          n_beds, window_seconds=float(seconds))
    for b in range(n_beds):
        ingest.ingest(0.0, b, "ecg", clips[b])
    refs = [ingest.close_window(b, float(seconds)) for b in range(n_beds)]
    return ingest, refs, clips


def _reference(members, clips: np.ndarray) -> np.ndarray:
    """[M, beds] float64 member scores from the plain per-member
    forward pass in float32 at the highest matmul precision."""
    import jax
    import jax.numpy as jnp
    from repro.models.ecg_resnext import ecg_apply
    apply = jax.jit(ecg_apply, static_argnames=("spec",))
    out = np.zeros((len(members), clips.shape[0]))
    with jax.default_matmul_precision("highest"):
        for i, m in enumerate(members):
            x = jnp.asarray(clips[:, m.spec.lead, -m.spec.input_len:, None])
            logits = np.asarray(apply(m.params, x, spec=_blank(m.spec)),
                                np.float64)
            out[i] = 1.0 / (1.0 + np.exp(logits[:, 0] - logits[:, 1]))
    return out


def _serve(service, ingest, refs, log):
    """Slot engine (admit, ticks, reads) then the flush path, on the same
    refs.  Returns (slot scores, flush scores, member score matrix,
    warm seconds)."""
    from repro.serving.slots import SlotEngine
    engine = SlotEngine(service, ingest)
    t0 = time.perf_counter()
    engine.warm()
    warm_s = time.perf_counter() - t0
    n = len(refs)
    for r in refs:
        engine.admit(r.patient)
        engine.update(r)
    tick_ms = []
    for _ in range(N_TICKS):
        rep = engine.tick()
        _check(rep.n_scored == n and rep.n_stale == 0,
               f"tick scored {rep.n_scored}/{n} slots, {rep.n_stale} stale")
        tick_ms.append(rep.seconds * 1e3)
    reps = 200
    t0 = time.perf_counter()
    for _ in range(reps):
        slot = np.asarray([engine.read(s) for s in range(n)])
    read_us = (time.perf_counter() - t0) / (reps * n) * 1e6
    members = np.zeros((len(service.members), n))
    for g in engine.groups:
        members[g.rows] = np.asarray(g.state)[:, :n]
    flush = np.asarray(service.predict_batch(refs))
    log(f"  warm (compile + one run per program): {warm_s:.1f} s")
    log(f"  tick ms (smoke reading, not a metric): "
        + ", ".join(f"{t:.1f}" for t in tick_ms))
    log(f"  read us/slot (smoke reading, not a metric): {read_us:.3f}")
    _check(np.all(np.isfinite(slot)), "a slot read returned NaN")
    _check(np.array_equal(slot, flush),
           f"slot scores differ from flush scores: max "
           f"{np.max(np.abs(slot - flush)):.3g}")
    log("  slot scores == flush scores bitwise")
    return slot, flush, members, warm_s


def _peak_bytes(device) -> str:
    stats = device.memory_stats()
    if not stats or "peak_bytes_in_use" not in stats:
        return "not reported by this backend"
    return str(stats["peak_bytes_in_use"])


def _run_one_chip(members, ingest, refs, clips, *, pallas_impl: str,
                  log) -> None:
    import jax
    from repro.configs.ecg_zoo import bucket_zoo
    from repro.serving.pipeline import EnsembleService

    log("serving: slot engine + flush path (impl=xla)")
    service = EnsembleService(members)
    slot, _, member_mat, warm_s = _serve(service, ingest, refs, log)

    t0 = time.perf_counter()
    ref_mat = _reference(members, clips)
    ref_s = time.perf_counter() - t0
    ref = ref_mat.mean(axis=0)
    dev = float(np.max(np.abs(slot - ref)))
    dev_member = float(np.max(np.abs(member_mat - ref_mat)))
    log(f"reference (float32, highest precision): {ref_s:.1f} s")
    log(f"max abs deviation from reference: ensemble {dev:.3g}, "
        f"single member {dev_member:.3g} (tolerance {SCORE_TOL:g} on "
        f"the ensemble score)")
    _check(dev <= SCORE_TOL, f"ensemble deviation {dev:.3g} > "
           f"{SCORE_TOL:g}")

    specs = [m.spec for m in members]
    key, idx = max(bucket_zoo(specs).items(),
                   key=lambda kv: (kv[0][0], kv[0][1]))
    bucket = [members[i] for i in idx]
    log(f"stripe-conv kernel: bucket width {key[0]}, {key[1]} blocks, "
        f"{len(idx)} members, impl={pallas_impl} vs xla")
    kernel_svc = EnsembleService(bucket, impl=pallas_impl)
    t0 = time.perf_counter()
    kernel_svc.warmup(batch_sizes=(len(refs),))
    kernel_s = time.perf_counter() - t0
    got = np.asarray(kernel_svc.predict_batch(refs))
    want = np.asarray(EnsembleService(bucket).predict_batch(refs))
    d_xla = float(np.max(np.abs(got - want)))
    d_ref = float(np.max(np.abs(got - ref_mat[idx].mean(axis=0))))
    log(f"  warm (compile + one run): {kernel_s:.1f} s")
    log(f"  max abs difference: kernel vs xla {d_xla:.3g}, kernel vs "
        f"reference {d_ref:.3g}")
    _check(np.all(np.isfinite(got)), "kernel bucket returned NaN")
    _check(d_xla <= SCORE_TOL and d_ref <= SCORE_TOL,
           f"kernel bucket off by {max(d_xla, d_ref):.3g}")
    log(f"compile seconds (set-up): serve {warm_s:.1f}, reference "
        f"{ref_s:.1f}, kernel {kernel_s:.1f}, total "
        f"{warm_s + ref_s + kernel_s:.1f}")
    log(f"peak_bytes_in_use: {_peak_bytes(jax.devices()[0])}")


def _run_sharded(members, ingest, refs, chips: int, *, log) -> None:
    import jax
    from repro.serving.pipeline import EnsembleService

    devices = jax.devices()[:chips]
    base = EnsembleService(members)
    plan = base.plan_placement(chips,
                               bucket_costs=base.analytic_bucket_costs())
    log(f"placement over {chips} chips (LPT on MACs): members per chip "
        f"{[len(s) for s in plan.assignment]}")

    log("one chip:")
    one, one_flush, _, _ = _serve(base, ingest, refs, log)
    log(f"{chips} chips:")
    sharded_svc = EnsembleService(members, placement=plan,
                                  devices=devices)
    sharded, sharded_flush, _, _ = _serve(sharded_svc, ingest, refs, log)

    held = {}
    for b in sharded_svc._buckets:
        for leaf in jax.tree.leaves(b.stacked):
            _check(leaf.devices() == {b.device},
                   f"a bucket placed on {b.device} has a parameter on "
                   f"{leaf.devices()}")
        held[b.device] = held.get(b.device, 0) + len(b.idx)
    _check(set(held) == set(devices),
           f"shards landed on {sorted(map(str, held))}, not on all "
           f"{chips} chips")
    for d in devices:
        stats = d.memory_stats() or {}
        log(f"  {d}: {held[d]} members, bytes_in_use "
            f"{stats.get('bytes_in_use', 'not reported')}, "
            f"peak_bytes_in_use {stats.get('peak_bytes_in_use', 'n/a')}")
        _check(not stats or stats.get("bytes_in_use", 0) > 0,
               f"{d} holds no bytes")

    diff = max(float(np.max(np.abs(sharded - one))),
               float(np.max(np.abs(sharded_flush - one_flush))))
    bitwise = bool(np.array_equal(sharded, one)
                   and np.array_equal(sharded_flush, one_flush))
    log(f"{chips} chips vs one chip: max abs difference {diff:.3g}, "
        f"bitwise equal: {bitwise}")
    _check(diff <= SHARD_TOL, f"sharded scores off by {diff:.3g}")


def run(*, reduced: bool = False, n_beds: int = N_BEDS, seed: int = 0,
        chips: int = 1, platform: str = "tpu",
        pallas_impl: str = "pallas",
        log=functools.partial(print, flush=True)) -> dict:
    """The whole smoke run in this process.  ``reduced``, ``n_beds``,
    ``platform`` and ``pallas_impl`` let a test drive the same phases
    at a small size on another backend.  Returns the device record of
    the result line; raises ``SmokeFailure`` when a check fails."""
    import jax
    devices = jax.devices()
    d0 = devices[0]
    log(f"device: platform={d0.platform} kind={d0.device_kind} "
        f"count={len(devices)}")
    _check(d0.platform == platform,
           f"JAX found platform {d0.platform!r}, this run needs "
           f"{platform!r}")
    _check(len(devices) >= chips,
           f"{chips} chips asked for, JAX found {len(devices)}")

    t0 = time.perf_counter()
    members = _build_zoo(reduced, seed)
    from repro.configs.ecg_zoo import bucket_zoo
    n_buckets = len(bucket_zoo([m.spec for m in members]))
    ingest, refs, clips = _census(members, n_beds, seed)
    log(f"zoo: {len(members)} members in {n_buckets} buckets, "
        f"{n_beds} beds, window {clips.shape[-1]} samples x "
        f"{clips.shape[1]} leads ({time.perf_counter() - t0:.1f} s to "
        f"build and ingest)")

    if chips == 1:
        _run_one_chip(members, ingest, refs, clips,
                      pallas_impl=pallas_impl, log=log)
    else:
        _run_sharded(members, ingest, refs, chips, log=log)
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded placement path and the "
                         "one-chip run it is compared with")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    from repro.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    device = run(chips=args.chips, seed=args.seed)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
