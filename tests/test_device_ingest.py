"""Device-resident streaming ingest + on-device lead-gather:

* ring-phase correctness: ``write_idx`` wraps at a MULTIPLE of the
  capacity (regression for the ``% 2**30`` shear on non-pow2 caps);
* ``ingest_chunk``'s pow2 chunk ladder — semantics equal to the
  per-length ``ingest_step``, compiled-variant count bounded under
  mixed-rate feeds;
* the Pallas ``window_gather`` kernel against the jnp oracle
  (interpret mode), including wraparound / dropout / padding rows;
* THE acceptance property: device-resident ingest + on-device
  lead-gather scores BITWISE-identical to the ``PatientAggregator`` +
  host-marshaling path, across ring wraparound, sensor dropout
  (zero-fill), short-window left-padding, every pow2 flush-ladder
  rung, and (via the ``multi_device`` lane) the sharded 8-device path;
* the warmed pow2 flush ladder: no compile on the flush path after
  ``warmup()``;
* device refs flowing through the batch-aware server and a zero-drop
  hot swap mid-stream.
"""
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ref as kref
from repro.serving.aggregator import (DeviceIngest, ModalitySpec,
                                      agg_init, chunk_rung,
                                      gather_windows, ingest_chunk,
                                      ingest_step, read_window_static,
                                      ring_wrap)
from repro.serving.pipeline import EnsembleService, StreamingPipeline

N_FORCED = 8
IN_LANE = jax.device_count() >= N_FORCED
multi_device = pytest.mark.multi_device
needs_devices = pytest.mark.skipif(
    not IN_LANE,
    reason=f"needs {N_FORCED} forced host devices (CI lane or the "
           "subprocess wrapper below)")


# ------------------------------------------------------------ ring phase
def test_ring_wrap_is_multiple_of_capacity():
    for cap in (1, 7, 8, 10, 12, 100, 512, 7500, 2 ** 20):
        w = ring_wrap(cap)
        assert w % cap == 0
        assert 0 < w <= 2 ** 30
    assert ring_wrap(512) == 2 ** 30      # pow2 caps keep the old wrap


def test_write_idx_wrap_preserves_ring_phase_non_pow2_cap():
    """Regression: wrapping ``write_idx`` at a modulus that is NOT a
    multiple of the capacity shears the ring after the wrap (the old
    ``% 2**30`` with e.g. cap=12).  Seed the counter just below the
    wrap point and stream across it: the ring must stay consistent
    with a plain host-side tail."""
    cap = 12                              # does not divide 2**30
    st = agg_init(n_patients=1, channels=1, capacity=cap)
    wrap = ring_wrap(cap)
    # shifting write_idx by a multiple of cap is semantically inert,
    # so this fast-forward is equivalent to actually streaming
    # wrap - 2*cap samples
    st = st._replace(write_idx=st.write_idx + (wrap - 2 * cap))
    stream = []
    rng = np.random.default_rng(0)
    for k in (5, 7, 4, 9, 6):             # 31 samples: crosses wrap
        c = rng.standard_normal((1, k)).astype(np.float32)
        stream.append(c)
        st = ingest_chunk(st, 0, c)
    full = np.concatenate(stream, -1)
    got = np.asarray(read_window_static(st, 0, cap))
    np.testing.assert_array_equal(got, full[:, -cap:])
    assert int(st.write_idx[0]) < wrap    # counter actually wrapped


def test_ingest_step_wrap_matches_chunk_path():
    st_a = agg_init(1, 2, 8)
    st_b = agg_init(1, 2, 8)
    rng = np.random.default_rng(1)
    for k in (3, 1, 5, 2, 8, 4):
        c = rng.standard_normal((2, k)).astype(np.float32)
        st_a = ingest_step(st_a, jnp.asarray(0), jnp.asarray(c))
        st_b = ingest_chunk(st_b, 0, c)
    np.testing.assert_array_equal(np.asarray(st_a.buf),
                                  np.asarray(st_b.buf))
    assert int(st_a.total[0]) == int(st_b.total[0]) == 23


# ------------------------------------------------------------ chunk ladder
def test_chunk_rung_is_pow2_ladder():
    assert [chunk_rung(k) for k in (1, 2, 3, 4, 5, 9, 250, 257)] \
        == [1, 2, 4, 4, 8, 16, 256, 512]


def test_ingest_chunk_bounded_retrace_under_mixed_rates():
    """Mixed-rate feeds (every chunk length 1..64) must compile at most
    one variant per pow2 rung, not one per length."""
    from repro.serving.aggregator import _ingest_padded
    st = agg_init(1, 1, 128)
    before = _ingest_padded._cache_size()
    lens = list(range(1, 65))
    np.random.default_rng(2).shuffle(lens)
    for k in lens:
        st = ingest_chunk(st, 0, np.zeros((1, k), np.float32))
    grew = _ingest_padded._cache_size() - before
    assert grew <= len({chunk_rung(k) for k in lens}) == 7
    assert int(st.total[0]) == sum(lens)


def test_ingest_chunk_rejects_oversized_chunk():
    st = agg_init(1, 1, 16)
    with pytest.raises(ValueError):
        ingest_chunk(st, 0, np.zeros((1, 17), np.float32))


# --------------------------------------------------- window-gather kernel
def _random_ring(rng, n=3, c=2, cap=16, feeds=(11, 30, 5)):
    st = agg_init(n, c, cap)
    streams = {p: [] for p in range(n)}
    for p, total in enumerate(feeds):
        off = 0
        while off < total:
            k = min(int(rng.integers(1, 7)), total - off)
            chunk = rng.standard_normal((c, k)).astype(np.float32)
            streams[p].append(chunk)
            st = ingest_chunk(st, p, chunk)
            off += k
    return st, {p: (np.concatenate(s, -1) if s
                    else np.zeros((c, 0), np.float32))
                for p, s in streams.items()}


def test_window_gather_ref_semantics():
    rng = np.random.default_rng(3)
    st, streams = _random_ring(rng)                   # feeds wrap cap=16
    L = 8
    patients = jnp.asarray([2, 0, 1, 0], jnp.int32)
    ends = jnp.asarray([5, 11, 30 % 16, 11], jnp.int32)
    valid = jnp.asarray([5, 8, 8, 3], jnp.int32)      # incl. dropout row
    got = np.asarray(gather_windows(st.buf, patients, ends, valid, L))
    for i, (p, e, v) in enumerate(((2, 5, 5), (0, 11, 8),
                                   (1, 30, 8), (0, 11, 3))):
        tail = streams[p][:, :e][:, -min(v, L):]
        want = np.zeros((2, L), np.float32)
        if tail.shape[-1]:
            want[:, L - tail.shape[-1]:] = tail
        np.testing.assert_array_equal(got[i], want)


def test_window_gather_pallas_matches_ref():
    from repro.kernels.window_gather import window_gather
    rng = np.random.default_rng(4)
    st, _ = _random_ring(rng, n=4, c=3, cap=32, feeds=(40, 7, 33, 0))
    L = 16
    patients = jnp.asarray([0, 3, 2, 1], jnp.int32)
    ends = jnp.asarray([40 % 32, 0, 33 % 32, 7], jnp.int32)
    valid = jnp.asarray([16, 0, 9, 7], jnp.int32)     # pad row: valid=0
    want = kref.window_gather(st.buf, patients, ends, valid, L)
    got = window_gather(st.buf, patients, ends, valid, L,
                        interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert np.asarray(got)[1].sum() == 0.0            # padding row zero


# --------------------------------------------- service-level equivalence
def _ingest_windows(windows, window_seconds=1.0, chunks=(100, 75, 75)):
    """Stream host windows into a DeviceIngest and close one ref per
    patient; chunk sizes exercise the pow2 ladder."""
    di = DeviceIngest([ModalitySpec("ecg", 250.0, 3)],
                      n_patients=len(windows),
                      window_seconds=window_seconds)
    refs = []
    for p, w in enumerate(windows):
        ecg, off = np.asarray(w["ecg"], np.float32), 0
        for k in chunks:
            if off >= ecg.shape[-1]:
                break
            di.ingest(off / 250.0, p, "ecg", ecg[:, off:off + k])
            off += k
        while off < ecg.shape[-1]:
            di.ingest(off / 250.0, p, "ecg", ecg[:, off:off + 100])
            off += 100
        refs.append(di.close_window(p, window_seconds))
    return di, refs


def test_refs_bitwise_every_ladder_rung(zoo_members, rng):
    """Device-resident flushes match the host-marshaled pack BITWISE at
    every pow2 flush rung (and the odd sizes that pad up to them)."""
    svc = EnsembleService(zoo_members)
    windows = [{"ecg": rng.standard_normal((3, 250)).astype(np.float32)}
               for _ in range(8)]
    _, refs = _ingest_windows(windows)
    for P in (1, 2, 3, 5, 8):
        want = svc.predict_batch(windows[:P])
        got = svc.predict_batch(refs[:P])
        assert np.array_equal(np.asarray(got), np.asarray(want)), P


def test_refs_bitwise_short_window_left_padding(zoo_members, rng):
    """A window holding fewer samples than input_len is left-zero-padded
    identically on both paths."""
    svc = EnsembleService(zoo_members)
    windows = [{"ecg": rng.standard_normal((3, n)).astype(np.float32)}
               for n in (40, 100, 249)]
    _, refs = _ingest_windows(windows, chunks=(30, 30, 40))
    got = svc.predict_batch(refs)
    want = svc.predict_batch(windows)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_refs_bitwise_after_ring_wraparound(zoo_members, rng):
    """Several windows per patient: the ring (capacity 2 windows) wraps
    and the LAST window must still score bitwise-identically."""
    svc = EnsembleService(zoo_members)
    di = DeviceIngest([ModalitySpec("ecg", 250.0, 3)], n_patients=2,
                      window_seconds=1.0)
    cap = di.states["ecg"].buf.shape[-1]
    last = {}
    ref = {}
    for p in range(2):
        for w in range(4):                 # 4 x 250 samples > cap=512
            ecg = rng.standard_normal((3, 250)).astype(np.float32)
            for off in range(0, 250, 50):
                di.ingest(w + off / 250.0, p, "ecg",
                          ecg[:, off:off + 50])
            ref[p] = di.close_window(p, w + 1.0)
            last[p] = ecg
        assert int(di.fed["ecg"][p]) == 1000 > cap
    got = svc.predict_batch([ref[0], ref[1]])
    want = svc.predict_batch([{"ecg": last[0]}, {"ecg": last[1]}])
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_refs_bitwise_sensor_dropout_zero_fill(zoo_members, rng):
    """Dropout mid-window: only 120 of 250 samples arrive; both paths
    zero-fill the missing head."""
    svc = EnsembleService(zoo_members)
    windows = [{"ecg": rng.standard_normal((3, 120)).astype(np.float32)}
               for _ in range(3)]
    _, refs = _ingest_windows(windows, chunks=(50, 50, 20))
    assert all(r.valid["ecg"] == 120 for r in refs)
    got = svc.predict_batch(refs)
    want = svc.predict_batch(windows)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_stale_ref_refused_not_silently_wrong(zoo_members, rng):
    """A ref whose ring region has been overwritten by later ingest
    must be REFUSED (the server's safe-batch wrapper then NaNs only the
    stale query) — never silently served with the wrong window's
    samples.  Refs within the capacity slack still serve bitwise."""
    svc = EnsembleService(zoo_members)
    di = DeviceIngest([ModalitySpec("ecg", 250.0, 3)], n_patients=1,
                      window_seconds=1.0)                 # cap = 512
    first = rng.standard_normal((3, 250)).astype(np.float32)
    di.ingest(0.0, 0, "ecg", first)
    ref = di.close_window(0, 1.0)
    # one more full window: 500 <= cap, the ref is still intact
    di.ingest(1.0, 0, "ecg",
              rng.standard_normal((3, 250)).astype(np.float32))
    got = svc.predict_batch([ref])
    assert np.array_equal(np.asarray(got),
                          np.asarray(svc.predict_batch([{"ecg":
                                                         first}])))
    # a third window pushes ingest past cap beyond the ref's window
    di.ingest(2.0, 0, "ecg",
              rng.standard_normal((3, 250)).astype(np.float32))
    with pytest.raises(ValueError, match="stale"):
        svc.predict_batch([ref])
    # the unfused oracle path reads back via host_window: same guard
    with pytest.raises(ValueError, match="stale"):
        EnsembleService(zoo_members, fused=False).predict(ref)


def test_stale_vitals_ring_refused(zoo_members, rng):
    """The low-rate vitals ring overruns on its own clock: a ref whose
    VITALS window was overwritten must be refused even while its ECG
    window is still intact."""
    class Const:
        def predict_proba(self, x):
            return np.full(len(x), 0.5)

    svc = EnsembleService(zoo_members, vitals_model=Const())
    di = DeviceIngest([ModalitySpec("ecg", 250.0, 3),
                       ModalitySpec("vitals", 1.0, 7)],
                      n_patients=1, window_seconds=1.0)  # vitals cap=2
    di.ingest(0.0, 0, "ecg",
              rng.standard_normal((3, 250)).astype(np.float32))
    di.ingest(0.0, 0, "vitals",
              rng.standard_normal((7, 1)).astype(np.float32))
    ref = di.close_window(0, 1.0)
    assert 0.0 <= svc.predict(ref) <= 1.0      # fresh: serves fine
    di.ingest(1.0, 0, "vitals",
              rng.standard_normal((7, 2)).astype(np.float32))
    with pytest.raises(ValueError, match="vitals ring"):
        svc.predict(ref)                       # ECG intact, vitals gone


def test_refs_with_cpu_side_models(zoo_members, rng):
    """Vitals/labs CPU-side models join the bag identically: labs ride
    the ref's host side channel, vitals are read back from the ring."""
    class Const:
        def __init__(self, v):
            self.v = v

        def predict_proba(self, x):
            return np.full(len(x), self.v)

    svc = EnsembleService(zoo_members, vitals_model=Const(0.9),
                          labs_model=Const(0.1))
    di = DeviceIngest([ModalitySpec("ecg", 250.0, 3),
                       ModalitySpec("vitals", 1.0, 7)],
                      n_patients=1, window_seconds=1.0)
    ecg = rng.standard_normal((3, 250)).astype(np.float32)
    vit = rng.standard_normal((7, 1)).astype(np.float32)
    labs = rng.standard_normal(8).astype(np.float32)
    di.ingest(0.0, 0, "ecg", ecg)
    di.ingest(0.0, 0, "vitals", vit)
    r = di.close_window(0, 1.0, extra={"labs": labs})
    host_vit = np.zeros((7, 1), np.float32)
    host_vit[:, :] = vit                   # want=1 sample at 1 Hz
    want = svc.predict({"ecg": ecg, "vitals": host_vit, "labs": labs})
    assert svc.predict(r) == want
    # and without the models attached, the ref path never reads back
    bare = EnsembleService(zoo_members)
    assert bare.predict(r) == bare.predict({"ecg": ecg})


def test_refs_reject_legacy_marshal_and_mixed_ingest(zoo_members, rng):
    legacy = EnsembleService(zoo_members, marshal="legacy")
    windows = [{"ecg": rng.standard_normal((3, 250)).astype(np.float32)}
               for _ in range(2)]
    _, refs_a = _ingest_windows(windows[:1])
    _, refs_b = _ingest_windows(windows[1:])
    with pytest.raises(ValueError):
        legacy.predict_batch(refs_a)
    svc = EnsembleService(zoo_members)
    with pytest.raises(ValueError):
        svc.predict_batch([refs_a[0], refs_b[0]])
    with pytest.raises(ValueError):
        EnsembleService(zoo_members, marshal="nope")


def test_legacy_marshal_matches_packed(zoo_members, rng):
    """The preserved pre-refactor marshaling loop is still a correct
    oracle for the packed path."""
    windows = [{"ecg": rng.standard_normal((3, 250)).astype(np.float32)}
               for _ in range(5)]
    packed = EnsembleService(zoo_members)
    legacy = EnsembleService(zoo_members, marshal="legacy")
    np.testing.assert_allclose(packed.predict_batch(windows),
                               legacy.predict_batch(windows),
                               atol=1e-6)
    # the packed pack ships 3 leads once vs M member rows: M/3 less H2D
    assert legacy.h2d_bytes == 4 * packed.h2d_bytes


# ------------------------------------------------- pipeline equivalence
def _drive(pipe, feed):
    return [r.score for r in filter(None, (
        pipe.feed(t, p, m, s) for (t, p, m, s) in feed))]


def _full_rate_feed(rng, n_patients=2, n_windows=3, chunk=25,
                    window=1.0, drop=()):
    """Aligned contract feed: a uniform stream of ``chunk``-sample ECG
    bursts every chunk/250 s per patient starting at t=0, so every
    window closes exactly at its boundary (on the burst whose arrival
    crosses it).  ``drop`` lists (patient, burst_idx) bursts to
    withhold (sensor dropout) — never burst 0 or a window-closing
    burst, and only in the FIRST window under this boundary-aligned
    feed: the oracle's time-based retention re-reads a window-closing
    burst in the next window (count-based accounting attributes it to
    the window it closed), and only a full next window slices that
    boundary sample back out.  Arbitrary-window dropout is covered at
    the service level, where close times are explicit."""
    feed = []
    per_w = int(round(250 * window)) // chunk
    for j in range(n_windows * per_w + 1):
        t = j * (chunk / 250.0)
        for p in range(n_patients):
            if (p, j) in drop:
                continue
            feed.append((t, p, "ecg", rng.standard_normal(
                (3, chunk)).astype(np.float32)))
    return feed


def test_pipeline_device_vs_host_bitwise(zoo_members, rng):
    """End-to-end StreamingPipeline equivalence: same service, same
    stream, device rings vs python aggregators — identical scores,
    across enough windows to wrap the ring."""
    svc = EnsembleService(zoo_members)
    host = StreamingPipeline(svc, n_patients=2, window_seconds=1.0)
    dev = StreamingPipeline(svc, n_patients=2, window_seconds=1.0,
                            device_ingest=True)
    feed = _full_rate_feed(rng, n_windows=3)
    got, want = _drive(dev, feed), _drive(host, feed)
    assert len(want) == 2 * 3              # every window served
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert int(dev.device_ingest.fed["ecg"][0]) == 775 \
        > dev.device_ingest.states["ecg"].buf.shape[-1]   # wrapped


def test_pipeline_device_vs_host_with_dropout(zoo_members, rng):
    svc = EnsembleService(zoo_members)
    host = StreamingPipeline(svc, n_patients=2, window_seconds=1.0)
    dev = StreamingPipeline(svc, n_patients=2, window_seconds=1.0,
                            device_ingest=True)
    drop = {(0, 3), (0, 4), (1, 6)}      # first-window mid dropouts
    feed = _full_rate_feed(rng, n_windows=3, drop=drop)
    got, want = _drive(dev, feed), _drive(host, feed)
    assert np.array_equal(np.asarray(got), np.asarray(want))


@multi_device
@needs_devices
def test_refs_bitwise_sharded_8_devices(zoo_members, rng):
    """The forced-8-device lane: device-resident flushes through a
    sharded placement equal the unsharded host path bitwise — the
    gathered pack is copied once per shard device, never per member."""
    from repro.configs.ecg_zoo import bucket_zoo
    from repro.serving.placement import grouped_lpt_placement
    groups = list(bucket_zoo([m.spec for m in zoo_members]).values())
    pl = grouped_lpt_placement(groups, [1.0 + 0.1 * j for j in
                                        range(len(groups))], N_FORCED)
    sharded = EnsembleService(zoo_members, placement=pl,
                              devices=jax.devices()[:N_FORCED])
    flat = EnsembleService(zoo_members)
    windows = [{"ecg": rng.standard_normal((3, 250)).astype(np.float32)}
               for _ in range(6)]
    _, refs = _ingest_windows(windows)
    want = flat.predict_batch(windows)
    assert np.array_equal(np.asarray(sharded.predict_batch(refs)),
                          np.asarray(want))
    assert np.array_equal(np.asarray(sharded.predict_batch(windows)),
                          np.asarray(want))


@pytest.mark.skipif(IN_LANE, reason="already in the multi-device lane")
def test_multi_device_lane_subprocess():
    """Single-device lane: re-run this module's ``multi_device``
    selection under 8 forced host devices."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count"
                        f"={N_FORCED}")
    env["JAX_PLATFORMS"] = "cpu"          # forced HOST devices
    env.pop("PYTEST_CURRENT_TEST", None)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         os.path.abspath(__file__), "-m", "multi_device"],
        cwd=repo, env=env, capture_output=True, text=True, timeout=900)
    tail = (r.stdout or "") + (r.stderr or "")
    assert r.returncode == 0, tail[-4000:]
    assert " passed" in r.stdout, tail[-2000:]
    assert " skipped" not in r.stdout, tail[-2000:]


# ----------------------------------------------------------- headroom
def test_headroom_min_across_modalities_not_ecg_only(rng):
    """Regression: ``DeviceIngest.headroom`` hardcoded the ECG ring, so
    a vitals ring about to overrun reported full slack and the
    backpressure guard admitted queries that went stale-then-NaN
    downstream.  The aggregate signal is now the MIN across modalities
    in window units (< 1.0 => shed); the per-ring sample views survive
    via the modality arg and ``headroom_by_modality``."""
    di = DeviceIngest([ModalitySpec("ecg", 250.0, 3),
                       ModalitySpec("vitals", 1.0, 7)],
                      n_patients=1, window_seconds=1.0)
    di.ingest(0.0, 0, "ecg",
              np.zeros((3, 250), np.float32))
    di.ingest(0.0, 0, "vitals", np.zeros((7, 1), np.float32))
    di.close_window(0, 1.0)
    assert di.headroom(0) >= 1.0          # fresh: >= one window of slack
    # the low-rate vitals ring overruns on its OWN clock while the ECG
    # ring still has hundreds of samples of slack
    di.ingest(1.0, 0, "vitals", np.zeros((7, 2), np.float32))
    di.ingest(2.0, 0, "vitals", np.zeros((7, 1), np.float32))
    by_mod = di.headroom_by_modality(0)
    assert by_mod["ecg"] >= 250           # per-ring: ecg fine...
    assert by_mod["vitals"] < 1           # ...vitals exhausted
    assert di.headroom(0, "ecg") == by_mod["ecg"]
    # pre-fix the aggregate WAS the ecg number (hundreds of samples);
    # now it must surface the vitals overrun as backpressure
    assert di.headroom(0) < 1.0


# ------------------------------------------------------- warmup ladder
def test_warmup_compiles_full_flush_ladder(zoo_members, rng):
    """After default ``warmup()`` every pow2 flush size 1..8 hits a
    compiled program: no bucket dispatch compiles on the flush path."""
    svc = EnsembleService(zoo_members)
    svc.warmup()
    sizes = {id(b.fn): b.fn._cache_size() for b in svc._buckets}
    for P in (1, 2, 3, 4, 5, 8):
        svc.predict_batch([{"ecg": rng.standard_normal((3, 250))
                            .astype(np.float32)}] * P)
    for b in svc._buckets:
        assert b.fn._cache_size() == sizes[id(b.fn)]


# ------------------------------------------------- server + hot swap
def test_server_serves_device_refs(zoo_members, rng):
    from repro.serving.server import EnsembleServer
    svc = EnsembleService(zoo_members)
    windows = [{"ecg": rng.standard_normal((3, 250)).astype(np.float32)}
               for _ in range(12)]
    _, refs = _ingest_windows(windows)
    want = {p: svc.predict_batch(windows[p:p + 1])[0]
            for p in range(12)}
    srv = EnsembleServer(batch_handler=svc.predict_batch, n_workers=2,
                         max_batch=4, max_wait_ms=2.0).start()
    for p, r in enumerate(refs):
        assert srv.submit(p, r)
    stats = srv.stop()
    assert stats.served == 12
    for p, score, *_ in srv.results():
        # float tolerance: the server coalesces refs into flushes of
        # its own sizes, and different pow2 pads are different XLA
        # programs (same contract as the host-dict batching tests)
        assert score == pytest.approx(want[p], abs=1e-6)


def test_hot_swap_zero_drop_with_device_refs(zoo_members, rng):
    """Selector hot-swaps mid-stream under device-resident ingest: no
    query dropped, post-swap scores equal a cold service on the new
    selector fed the same refs."""
    from repro.control.swap import HotSwapper
    from repro.serving.server import EnsembleServer
    n = len(zoo_members)
    sel_a = np.ones(n, np.int8)
    sel_b = np.zeros(n, np.int8)
    sel_b[::2] = 1
    windows = [{"ecg": rng.standard_normal((3, 250)).astype(np.float32)}
               for _ in range(18)]
    di, refs = _ingest_windows(windows)
    sw = HotSwapper(zoo_members, sel_a, warmup_batch_sizes=(1,))
    sw.stage(sel_b)
    srv = EnsembleServer(batch_handler=sw.facade.predict_batch,
                         n_workers=2, max_batch=1,
                         max_wait_ms=0.5).start()
    for p, r in enumerate(refs):
        if p == 9:
            sw.swap_to(sel_b)
        assert srv.submit(p, r)
    stats = srv.stop()
    assert stats.served == 18              # zero dropped across the swap
    cold = EnsembleService.for_selector(zoo_members, sel_b)
    scores = {p: s for p, s, *_ in srv.results()}
    for p in range(9, 18):
        assert scores[p] == cold.predict_batch([refs[p]])[0]


# ------------------------------------------------------- bench schema
def test_bench_ingest_smoke_schema():
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks.serving_bench import bench_ingest, \
        check_ingest_schema
    out = bench_ingest(n_patients=2, reps=1, input_len=250,
                       verbose=False, write_json=False)
    check_ingest_schema(out)
    assert out["h2d_reduction_x"] == pytest.approx(4.0)


# ------------------------------------------------------- staged ingest
# name: (ring, beds, packet lengths drawn per packet, samples per bed,
#        read the rings every n packets (0: only at the end), whether a
#        full stage or a bed's capacity must force an in-line commit)
STAGE_CASES = {
    "interleaved": (ModalitySpec("ecg", 250.0, 3), 4, (1, 7, 50, 125, 128),
                    300, 3, False),
    "wrap": (ModalitySpec("ecg", 250.0, 3), 2, (125,), 1500, 0, True),
    "full_stage": (ModalitySpec("ecg", 250.0, 3), 80, (7,), 14, 0, True),
    "long_chunk": (ModalitySpec("ecg", 250.0, 3), 3, (100, 300), 800, 4,
                   False),
    "vitals": (ModalitySpec("vitals", 1.0, 7), 3, (1, 2, 3), 150, 0, True),
}


@pytest.mark.parametrize("case", sorted(STAGE_CASES))
def test_staged_ring_matches_packet_at_a_time(case, rng):
    """Packets staged on the host and committed in batches leave the
    ring, its write index and its totals bitwise where the per-length
    ``ingest_step``, one packet at a time, leaves them."""
    spec, beds, lens, per_bed, read_every, forced = STAGE_CASES[case]
    window = 30.0 if spec.name == "vitals" else 1.0
    di = DeviceIngest([spec], n_patients=beds, window_seconds=window)
    one = agg_init(beds, spec.channels, di.cap[spec.name])
    fed = np.zeros(beds, int)
    i = 0
    while (fed < per_bed).any():
        for p in np.flatnonzero(fed < per_bed):
            k = min(int(rng.choice(lens)), per_bed - int(fed[p]))
            x = rng.standard_normal((spec.channels, k)).astype(np.float32)
            di.ingest(0.0, int(p), spec.name, x)
            one = ingest_step(one, jnp.asarray(int(p)), jnp.asarray(x))
            fed[p] += k
            i += 1
            if read_every and i % read_every == 0:
                di.states
    got = di.states[spec.name]
    np.testing.assert_array_equal(np.asarray(got.buf), np.asarray(one.buf))
    np.testing.assert_array_equal(np.asarray(got.write_idx),
                                  np.asarray(one.write_idx))
    np.testing.assert_array_equal(np.asarray(got.total), fed)
    np.testing.assert_array_equal(di.fed[spec.name], fed)
    stats = di.stats()
    assert (stats["full"] > 0) == forced
    assert (stats["direct"] > 0) == (case == "long_chunk")


@pytest.mark.parametrize("reader", ["tick", "flush", "host_window"])
def test_ref_closed_after_staged_packets_reads_them(reader, zoo_members,
                                                    rng):
    """A window whose packets are all still staged when it closes is
    read with those samples by every reader of the rings: a slot tick,
    a flush and the host read-back."""
    from repro.serving.slots import SlotEngine
    svc = EnsembleService(zoo_members)
    di = DeviceIngest([ModalitySpec("ecg", 250.0, 3)], n_patients=8,
                      window_seconds=1.0)
    windows = [rng.standard_normal((3, 250)).astype(np.float32)
               for _ in range(8)]
    refs = []
    for p, w in enumerate(windows):
        for off in (0, 125):
            di.ingest(off / 250.0, p, "ecg", w[:, off:off + 125])
        refs.append(di.close_window(p, 1.0))
    assert di.stats()["commits"] == 0          # all 16 packets staged
    if reader == "host_window":
        for r, w in zip(refs, windows):
            np.testing.assert_array_equal(r.host_window("ecg"), w)
    else:
        want = np.asarray(svc.predict_batch([{"ecg": w} for w in windows]))
        if reader == "tick":
            eng = SlotEngine(svc, di)
            for r in refs:
                eng.update(r)
            assert eng.tick().n_scored == 8
            got = np.asarray([eng.read(p) for p in range(8)])
        else:
            got = np.asarray(svc.predict_batch(refs))
        assert np.array_equal(got, want)
    assert di.stats()["commits"] == 1 and di.stats()["packets"] == 16


def test_headroom_commits_nothing():
    """The backpressure signal needs only host integers and the ring
    capacities: it leaves staged packets staged."""
    di = DeviceIngest([ModalitySpec("ecg", 250.0, 3),
                       ModalitySpec("vitals", 1.0, 7)],
                      n_patients=2, window_seconds=1.0)
    di.ingest(0.0, 0, "ecg", np.zeros((3, 125), np.float32))
    di.ingest(0.0, 0, "vitals", np.zeros((7, 1), np.float32))
    di.close_window(0, 1.0)
    assert di.headroom_by_modality(0) == {"ecg": 512 - 125, "vitals": 1}
    assert di.headroom(0) == 1.0
    assert di.stats()["commits"] == 0


def test_commit_program_compiles_once_at_setup(rng):
    """Building the ingest compiles the commit program at the stage's
    one shape; no mix of packet lengths up to the rung, read or forced
    commit compiles it again."""
    from repro.serving.aggregator import PACKET_RUNG, _ingest_padded
    di = DeviceIngest([ModalitySpec("ecg", 250.0, 3)], n_patients=5,
                      window_seconds=1.0)
    compiled = _ingest_padded._cache_size()
    lens = list(range(1, PACKET_RUNG + 1))
    rng.shuffle(lens)
    for i, k in enumerate(lens):
        di.ingest(0.0, i % 5, "ecg", np.zeros((3, k), np.float32))
        if i % 50 == 49:
            di.states
    di.states
    assert _ingest_padded._cache_size() == compiled
    stats = di.stats()
    assert stats["full"] > 0 and stats["packets"] == PACKET_RUNG


def test_stats_count_commits_and_packets():
    di = DeviceIngest([ModalitySpec("ecg", 250.0, 3)], n_patients=70,
                      window_seconds=1.0)
    z = np.zeros((3, 10), np.float32)
    for p in range(70):                 # the 65th finds the stage full
        di.ingest(0.0, p, "ecg", z)
    assert di.stats() == {"commits": 1, "packets": 64, "full": 1,
                          "direct": 0, "packets_per_commit": 64.0}
    # a chunk past the rung commits the six staged first, then goes in
    di.ingest(0.0, 0, "ecg", np.zeros((3, 300), np.float32))
    assert di.stats() == {"commits": 2, "packets": 70, "full": 1,
                          "direct": 1, "packets_per_commit": 35.0}
    di.states                           # nothing staged: no commit
    assert di.stats()["commits"] == 2
    for _ in range(3):
        di.ingest(0.0, 1, "ecg", z)
    assert di.stats()["commits"] == 2   # staged, not written
    np.testing.assert_array_equal(np.asarray(di.states["ecg"].total),
                                  di.fed["ecg"])
    assert di.stats()["commits"] == 3 and di.stats()["packets"] == 73


def test_ingest_and_tick_threads_match_serial_replay(zoo_members, rng):
    """One thread stages packets and closes windows while another ticks
    back to back: every close is scored exactly as a serial replay of
    the same stream scores it, with no error and no stale slot.  The
    feeder waits for each round to be scored before the next, so no
    window is overwritten under a tick (the ring holds two)."""
    from repro.serving.slots import SlotEngine
    beds, rounds, pk = 4, 5, 25
    stream = rng.standard_normal((beds, 3, rounds * 250)).astype(np.float32)
    svc = EnsembleService(zoo_members)

    def build():
        di = DeviceIngest([ModalitySpec("ecg", 250.0, 3)], n_patients=beds,
                          window_seconds=1.0)
        return di, SlotEngine(svc, di)

    def feed_round(di, eng, r):
        for off in range(r * 250, (r + 1) * 250, pk):
            for p in range(beds):
                di.ingest(off / 250.0, p, "ecg", stream[p, :, off:off + pk])
        return [eng.update(di.close_window(p, r + 1.0))
                for p in range(beds)]

    def scored(reports):
        return {(int(s), int(v)): float(x) for rep in reports
                for s, v, x in zip(rep.stamped, rep.versions, rep.scores)}

    di, eng = build()
    serial = []
    for r in range(rounds):
        feed_round(di, eng, r)
        serial.append(eng.tick())

    di, eng = build()
    reports, errors, done = [], [], threading.Event()

    def feeder():
        try:
            for r in range(rounds):
                versions = feed_round(di, eng, r)
                for p, v in enumerate(versions):
                    assert eng.wait_scored(p, v, timeout=60.0), (p, v)
        except BaseException as e:      # reported on the test thread
            errors.append(e)
        finally:
            done.set()

    def ticker():
        try:
            while not done.is_set():
                reports.append(eng.tick())
        except BaseException as e:
            errors.append(e)

    threads = [threading.Thread(target=feeder), threading.Thread(target=ticker)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=240.0)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors
    assert all(rep.n_stale == 0 and not rep.skipped for rep in reports)
    want = scored(serial)
    assert len(want) == beds * rounds
    assert scored(reports) == want
    assert di.stats()["packets"] == beds * rounds * 250 // pk


def test_staged_ingest_under_concurrent_feeders_and_readers(rng):
    """Twelve feeder threads (two beds each) stage packets while four
    readers commit the stage over and over, with the interpreter
    switching threads every few microseconds: no staged row, ``fed``
    count or ring update is lost.  Each bed's ring ends as a plain
    replay of its own stream leaves it."""
    beds, feeders, per_bed = 24, 12, 1500
    di = DeviceIngest([ModalitySpec("ecg", 250.0, 3)], n_patients=beds,
                      window_seconds=1.0)
    cap = di.cap["ecg"]
    streams = rng.standard_normal((beds, 3, per_bed)).astype(np.float32)
    cuts = {p: np.cumsum(rng.integers(1, 129, size=per_bed))
            for p in range(beds)}
    errors, done = [], threading.Event()

    def feed(mine):
        try:
            for p in mine:
                edges = [0] + [int(c) for c in cuts[p] if c < per_bed]
                for a, b in zip(edges, edges[1:] + [per_bed]):
                    di.ingest(0.0, p, "ecg", streams[p, :, a:b])
        except BaseException as e:      # reported on the test thread
            errors.append(e)

    def read():
        try:
            while not done.is_set():
                di.states
        except BaseException as e:
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        fs = [threading.Thread(target=feed, args=(range(f, beds, feeders),))
              for f in range(feeders)]
        rs = [threading.Thread(target=read) for _ in range(4)]
        for t in fs + rs:
            t.start()
        for t in fs:
            t.join(timeout=120.0)
        done.set()
        for t in rs:
            t.join(timeout=30.0)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in fs + rs)
    assert not errors, errors
    want = np.zeros((beds, 3, cap), np.float32)
    for i in range(per_bed):
        want[:, :, i % cap] = streams[:, :, i]
    st = di.states["ecg"]
    np.testing.assert_array_equal(np.asarray(st.buf), want)
    np.testing.assert_array_equal(np.asarray(st.total), per_bed)
    np.testing.assert_array_equal(di.fed["ecg"], per_bed)
    stats = di.stats()
    assert stats["commits"] > 1 and stats["packets"] == sum(
        len([c for c in cuts[p] if c < per_bed]) + 1 for p in range(beds))
