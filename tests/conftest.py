"""Shared fixtures.  NOTE: no XLA_FLAGS by default — smoke tests and
benches must see the single real CPU device; only launch/dryrun.py
forces 512 placeholder devices (and runs in its own process).

The EXCEPTION is the multi-device lane: setting ``REPRO_MULTI_DEVICE=1``
(or exporting ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
directly, as the CI lane does) forces 8 host devices BEFORE jax
initialises, so the ``multi_device``-marked placement tests run
in-process.  In the default single-device lane those tests skip and
``test_placement_serving.py``'s subprocess wrapper re-runs them in a
child with the flag set.

Heavy integration tests carry ``@pytest.mark.slow`` (registered below) so
``pytest -m "not slow"`` gives a fast signal; the shared zoo fixtures are
session-scoped so the default run builds/trains each zoo exactly once.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# env-guarded multi-device lane: must happen before anything imports jax
if os.environ.get("REPRO_MULTI_DEVICE"):
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np
import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavy integration test (deselect with -m 'not slow')")
    config.addinivalue_line(
        "markers",
        "multi_device: needs >= 8 forced host devices (XLA_FLAGS / "
        "REPRO_MULTI_DEVICE lane, or the subprocess wrapper in "
        "test_placement_serving.py)")


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def icu_data():
    from repro.training.data import make_icu_dataset, split_by_patient
    data = make_icu_dataset(n_patients=12, clips_per_patient=8, seed=0,
                            seconds=3)
    return split_by_patient(data, holdout=4)


@pytest.fixture(scope="session")
def small_zoo():
    """Trained reduced zoo + extras (cached on disk by zoo_setup);
    shared session-wide by integration/serving tests."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks.zoo_setup import build_zoo
    return build_zoo(n_patients=12, clips=6, steps=60, seconds=3,
                     verbose=False)


@pytest.fixture(scope="session")
def zoo_members():
    """Randomly-initialised reduced-zoo members (short clips) — the
    shared substrate for fused-serving/equivalence tests, where member
    WEIGHTS don't matter but shapes and bucketing do."""
    import jax
    from repro.configs.ecg_zoo import zoo_specs
    from repro.models.ecg_resnext import init_ecg
    from repro.serving.pipeline import ZooMember
    specs = zoo_specs(reduced=True, input_len=250)
    return [ZooMember(s, init_ecg(jax.random.PRNGKey(i), s))
            for i, s in enumerate(specs)]


def _eq5(member_scores, item, vitals_model, labs_model):
    """Eq. 5 written out plainly: the mean over the member scores and
    the attached side models' scores (vitals, then labs); 0.5 when
    there is nothing to bag."""
    scores = [float(s) for s in member_scores]
    for name, model in (("vitals", vitals_model), ("labs", labs_model)):
        if model is not None and item.get(name) is not None:
            scores.append(float(model.predict_proba(item[name][None])[0]))
    return float(np.mean(scores)) if scores else 0.5


class MemberLoop:
    """The plain per-member oracle: one ``ecg_apply`` call per member
    on its own lead, left-zero-padded to its input length, softmax,
    then Eq. 5 with the side models.  ``dispatch_count`` counts the
    member calls."""

    def __init__(self, members, vitals_model=None, labs_model=None):
        import jax
        from repro.models.ecg_resnext import ecg_apply
        self.members = list(members)
        self.vitals_model = vitals_model
        self.labs_model = labs_model
        self.dispatch_count = 0
        self._fns = [jax.jit(lambda x, p=m.params, s=m.spec: jax.nn.softmax(
            ecg_apply(p, x, s), axis=-1)[:, 1]) for m in self.members]

    def predict(self, item) -> float:
        col = []
        for m, fn in zip(self.members, self._fns):
            L = m.spec.input_len
            clip = np.asarray(item["ecg"], np.float32)[m.spec.lead, -L:]
            clip = np.pad(clip, (L - clip.shape[-1], 0))
            col.append(float(fn(clip[None, :, None])[0]))
            self.dispatch_count += 1
        return _eq5(col, item, self.vitals_model, self.labs_model)


def host_marshal_predict(service, batch):
    """The host-marshal oracle: each of ``service``'s buckets runs the
    stacked forward pass (``ecg_apply_stacked``, softmax) on an
    ``[M, Ppad, L, 1]`` input built on the host by a (member, patient)
    loop, then Eq. 5 per patient.  Same weights and stacking as the
    service; only where the lead expansion happens differs."""
    import jax
    from repro.models.ecg_resnext import ecg_apply_stacked
    from repro.serving.aggregator import pow2_rung
    P = len(batch)
    mat = np.zeros((len(service.members), P))
    for b in service._buckets:
        L = b.spec.input_len
        xs = np.zeros((len(b.idx), pow2_rung(P), L, 1), np.float32)
        for j, lead in enumerate(b.leads):
            for p, item in enumerate(batch):
                clip = np.asarray(item["ecg"], np.float32)[lead, -L:]
                xs[j, p, L - clip.shape[-1]:, 0] = clip
        y = jax.jit(lambda st, x, s=b.spec: jax.nn.softmax(
            ecg_apply_stacked(st, x, s, impl=service.impl),
            axis=-1)[..., 1])(b.stacked, xs)
        mat[b.idx] = np.asarray(y)[:, :P]
    return [_eq5(mat[:, p], item, service.vitals_model,
                 service.labs_model) for p, item in enumerate(batch)]


@pytest.fixture(scope="session")
def member_loop():
    """``MemberLoop``: the per-member oracle's constructor."""
    return MemberLoop


@pytest.fixture(scope="session")
def host_marshal():
    """``host_marshal_predict``: the host-marshal oracle."""
    return host_marshal_predict
