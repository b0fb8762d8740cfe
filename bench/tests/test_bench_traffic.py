"""The benchmark's copies of the program's generators are bitwise the
originals, and the schedule is what the mix says."""
import numpy as np

import traffic


def test_generators_match_program_bitwise():
    from repro.training import data
    for label in (0, 1):
        a = np.random.default_rng([2 ** 33 + 5, label])
        b = np.random.default_rng([2 ** 33 + 5, label])
        pa = traffic.sample_patient(a, label, atypicality=0.3)
        pb = data.sample_patient(b, label, atypicality=0.3)
        for f in ("heart_rate", "hrv", "noise", "st_offset"):
            assert getattr(pa, f) == getattr(pb, f)
        for f in ("vitals_base", "vitals_drift", "labs"):
            assert np.array_equal(getattr(pa, f), getattr(pb, f))
        assert np.array_equal(traffic.ecg_clip(a, pa, 37),
                              data.ecg_clip(b, pb, 37))
        assert np.array_equal(traffic.vitals_clip(a, pa, 30),
                              data.vitals_clip(b, pb, 30))
        assert np.array_equal(traffic.labs_sample(a, pa),
                              data.labs_sample(b, pb))


def test_schedule_spreads_closes_evenly():
    mix = dict(traffic.load_mix("unit64_hop5"))
    tr = traffic.build_traffic(mix, seed=2 ** 31 + 11, seconds=10.0,
                               window_s=30, vitals=False, labs=False)
    ev = tr.events
    closes = ev[ev[:, 1] == traffic.CLOSE]
    inwin = closes[(closes[:, 0] >= tr.window_start)
                   & (closes[:, 0] < tr.window_end)]
    assert len(inwin) == 64 * 10 / 5          # 12.8 closes/s
    gaps = np.diff(np.sort(closes[:, 0]))
    assert np.allclose(gaps, 5.0 / 64)
    # every close ends on a packet boundary, after the packet that ends it
    for t, _, b, j in closes:
        b, j = int(b), int(j)
        end = tr.close_ends[b, j]
        assert (end - tr.history) % tr.packet == 0
        k = (end - tr.history) // tr.packet - 1
        pk = ev[(ev[:, 1] == traffic.ECG) & (ev[:, 2] == b)
                & (ev[:, 3] == k)]
        assert len(pk) == 1 and pk[0, 0] == t
    assert tr.ecg.shape[-1] >= tr.close_ends.max()


def test_same_seed_same_traffic_other_seed_same_schedule():
    mix = dict(traffic.load_mix("unit64_hop5"), beds=100, hop_s=2.0)
    a = traffic.build_traffic(mix, 7, 4.0, 30, vitals=True, labs=True)
    b = traffic.build_traffic(mix, 7, 4.0, 30, vitals=True, labs=True)
    c = traffic.build_traffic(mix, 8, 4.0, 30, vitals=True, labs=True)
    assert np.array_equal(a.ecg, b.ecg) and np.array_equal(a.labs, b.labs)
    assert np.array_equal(a.events, c.events)
    assert not np.array_equal(a.ecg, c.ecg)
    # each live close sees the vitals the schedule fed up to its instant
    ev = a.events
    for t, _, bed, j in ev[ev[:, 1] == traffic.CLOSE]:
        fed = ((ev[:, 1] == traffic.VITALS) & (ev[:, 2] == bed)
               & (ev[:, 0] <= t)).sum()
        assert a.close_vends[int(bed), int(j)] == a.vitals_history + fed
