"""Shared benchmark substrate: trains the (reduced) ECG model zoo on the
synthetic ICU cohort, caches trained params + validation score vectors +
profiles, and exposes the accuracy/latency profilers every benchmark uses.

First call trains and caches under results/zoo_cache/; later calls load.
"""
from __future__ import annotations

import json
import os
import time
from typing import Dict, List, Tuple

import numpy as np

from repro.configs.ecg_zoo import EcgModelSpec, zoo_specs
from repro.core.bagging import bagging_predict, roc_auc
from repro.core.profiles import ModelProfile, ModelZoo, SystemConfig
from repro.models.ecg_resnext import ecg_macs, ecg_param_count
from repro.models.tabular import LogisticRegression, VitalsForest
from repro.serving.latency import LatencyProfiler
from repro.training import checkpoint
from repro.training.data import make_icu_dataset, split_by_patient
from repro.training.train_loop import (ecg_predict_proba, train_ecg_model)

CACHE = os.path.join(os.path.dirname(__file__), "..", "results",
                     "zoo_cache")


def build_zoo(reduced: bool = True, n_patients: int = 32,
              clips: int = 12, seconds: int = 3, steps: int = 160,
              seed: int = 0, verbose: bool = True, widths=None,
              blocks=None) -> Tuple[ModelZoo, Dict]:
    """Returns (zoo w/ cached val scores, extras dict)."""
    os.makedirs(CACHE, exist_ok=True)
    tag = f"r{int(reduced)}_p{n_patients}_c{clips}_s{seconds}_t{steps}" \
          f"_seed{seed}" + ("w" + "-".join(map(str, widths))
                            if widths else "") \
          + ("b" + "-".join(map(str, blocks)) if blocks else "")
    meta_path = os.path.join(CACHE, f"zoo_{tag}.json")

    data = make_icu_dataset(n_patients, clips, seed=seed, seconds=seconds)
    train, val = split_by_patient(data, holdout=max(4, n_patients // 3))
    specs = zoo_specs(reduced=reduced, input_len=seconds * 250,
                      widths=widths, blocks=blocks)

    profiles: List[ModelProfile] = []
    scores: List[np.ndarray] = []
    params_all = {}
    t0 = time.time()
    for i, spec in enumerate(specs):
        ck = os.path.join(CACHE, f"{tag}_{spec.name}.npz")
        x_tr = train["ecg"][:, spec.lead, :]
        from repro.models.ecg_resnext import init_ecg
        import jax
        template = init_ecg(jax.random.PRNGKey(seed + i), spec)
        if os.path.exists(ck):
            params = checkpoint.restore(ck, template)
        else:
            params, _ = train_ecg_model(spec, x_tr, train["label"],
                                        steps=steps, seed=seed + i)
            checkpoint.save(ck, params, {"spec": spec.name})
        sc = ecg_predict_proba(params, val["ecg"][:, spec.lead, :], spec)
        auc = roc_auc(val["label"] == 1, sc)
        profiles.append(ModelProfile(
            name=spec.name, depth=spec.blocks, width=spec.width,
            macs=ecg_macs(spec), memory_bytes=4.0 * ecg_param_count(params),
            modality=spec.lead, input_len=spec.input_len, val_auc=auc))
        scores.append(sc)
        params_all[spec.name] = params
        if verbose:
            print(f"[zoo] {spec.name}: val AUC {auc:.3f} "
                  f"({time.time() - t0:.0f}s)", flush=True)

    # CPU-side models (join the accuracy ensemble, not the latency zoo)
    vit = VitalsForest(n_channels=7, n_trees=15, seed=seed)
    vit.fit(train["vitals"], train["label"].astype(float))
    vit_scores = vit.predict_proba(val["vitals"])
    lab = LogisticRegression(steps=300, seed=seed)
    lab.fit(train["labs"], train["label"].astype(float))
    lab_scores = lab.predict_proba(val["labs"])

    zoo = ModelZoo(profiles, val_scores=np.stack(scores),
                   val_labels=(val["label"] == 1).astype(int))

    # measured per-member serving cost (closed-loop, jitted — the paper's
    # mu measurement), cached alongside the zoo under the device kind it
    # was measured on: another device's costs are not this one's
    import jax
    kind = "_".join(jax.devices()[0].device_kind.split())
    costs_path = os.path.join(CACHE, f"costs_{tag}_{kind}.json")
    if os.path.exists(costs_path):
        with open(costs_path) as f:
            measured = json.load(f)
    else:
        from repro.serving.pipeline import EnsembleService, ZooMember
        svc = EnsembleService([ZooMember(s, params_all[s.name])
                               for s in specs])
        cs = svc.measured_costs(reps=3)
        measured = {s.name: c for s, c in zip(specs, cs)}
        with open(costs_path, "w") as f:
            json.dump(measured, f)

    extras = {"train": train, "val": val, "params": params_all,
              "specs": specs, "vitals_scores": vit_scores,
              "labs_scores": lab_scores, "vitals_model": vit,
              "labs_model": lab,
              "measured_costs": [measured[s.name] for s in specs]}
    with open(meta_path, "w") as f:
        json.dump({"aucs": [p.val_auc for p in profiles]}, f)
    return zoo, extras


def make_profilers(zoo: ModelZoo, sysconf: SystemConfig,
                   extras: Dict = None, include_cpu_models: bool = True,
                   measured: bool = True):
    """(f_a, f_l): the paper's two profilers.  f_a evaluates the TRUE
    bagging ensemble on the validation set (side CPU models included per
    §4.1.1); f_l is the network-calculus latency profiler, fed by the
    MEASURED closed-loop per-member costs when available (§3.4)."""
    y = zoo.val_labels
    side = []
    if include_cpu_models and extras is not None:
        side = [extras["vitals_scores"], extras["labs_scores"]]

    def f_a(b) -> float:
        sel = zoo.val_scores[np.asarray(b, bool)]
        rows = list(sel) + side
        if not rows:
            return 0.5
        return roc_auc(y, np.mean(rows, axis=0))

    cost_fn = None
    if measured and extras is not None and "measured_costs" in extras:
        costs = extras["measured_costs"]
        cost_fn = lambda i: costs[i]
    f_l = LatencyProfiler(zoo, sysconf, cost_fn=cost_fn)
    return f_a, f_l


def binding_budget(zoo: ModelZoo, f_l, frac: float = 0.6) -> float:
    """A latency budget at which selection genuinely binds: frac x the
    latency of serving the ENTIRE zoo (the paper's 200 ms plays the same
    role against its 60-model zoo on 2 V100s)."""
    full = f_l(np.ones(len(zoo), np.int8))
    return float(frac * full)


def single_model_stats(zoo: ModelZoo, f_a, f_l):
    n = len(zoo)
    eye = np.eye(n, dtype=np.int8)
    acc = np.asarray([f_a(eye[i]) for i in range(n)])
    lat = np.asarray([f_l(eye[i]) for i in range(n)])
    return acc, lat
