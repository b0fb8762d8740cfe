"""Ensemble parallelism: HOLMES' bagging ensemble (Eq. 5) as a first-class
distributed feature on the multi-pod mesh.

The composer picks an ensemble b*; homogeneous members (same architecture,
different weights — e.g. the per-lead / per-seed ECG ResNeXts, or LM zoo
replicas fine-tuned per modality) are STACKED along a leading member axis
and shard_map-ped over the "pod" axis: each pod serves its member(s) on
its own (data, model) submesh and the final prediction is ONE cross-pod
psum of the [batch, n_classes] score — Eq. 5 as a collective.

Heterogeneous members fall back to per-pod programs placed by
serving/placement.py (plan_pod_ensemble).
"""
from __future__ import annotations

import functools
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map


def stack_members(member_params: list):
    """[params_0, params_1, ...] -> stacked pytree with leading member
    axis (members must be structurally identical)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *member_params)


def ensemble_serve(member_apply: Callable, mesh, n_members: int
                   ) -> Callable:
    """Build the ensemble-parallel serving step.

    member_apply(params_one_member, batch) -> scores [B, C]
    Returns step(stacked_params, batch) -> bagged scores [B, C]
    with members sharded over "pod" (each pod computes its members
    locally, then one psum over "pod" completes Eq. 5).
    """
    n_pods = mesh.shape.get("pod", 1)
    assert n_members % max(n_pods, 1) == 0, (n_members, n_pods)

    def local(params_local, batch):
        # params_local: leading axis = members on THIS pod
        scores = jax.vmap(lambda p: member_apply(p, batch))(params_local)
        total = jnp.sum(scores, axis=0)                 # [B, C]
        if n_pods > 1:
            total = jax.lax.psum(total, "pod")
        return total / n_members                        # Eq. 5 mean

    param_spec = jax.tree.map(lambda _: P("pod"), {"_": 0})["_"] \
        if n_pods > 1 else P()

    def specs_for(tree):
        return jax.tree.map(lambda _: param_spec, tree)

    def step(stacked_params, batch):
        in_specs = (specs_for(stacked_params),
                    jax.tree.map(lambda _: P(), batch))
        fn = shard_map(local, mesh=mesh, in_specs=in_specs,
                       out_specs=P())
        return fn(stacked_params, batch)

    return step


def dryrun_ensemble(n_members: int = 4, multi_pod: bool = True,
                    d: int = 512, verbose: bool = True) -> dict:
    """Compile the ensemble-parallel step on the production mesh with
    abstract member weights (a small MLP member as the stand-in)."""
    from repro.launch.mesh import make_production_mesh
    from repro.launch.dryrun import collective_bytes

    mesh = make_production_mesh(multi_pod=multi_pod)

    def member_apply(p, batch):
        h = jnp.tanh(batch["x"] @ p["w1"])
        return jax.nn.softmax(h @ p["w2"], axis=-1)

    member = {"w1": jax.ShapeDtypeStruct((d, d), jnp.bfloat16),
              "w2": jax.ShapeDtypeStruct((d, 2), jnp.bfloat16)}
    stacked = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((n_members,) + s.shape, s.dtype),
        member)
    batch = {"x": jax.ShapeDtypeStruct((64, d), jnp.bfloat16)}

    step = ensemble_serve(member_apply, mesh, n_members)
    with mesh:
        compiled = jax.jit(step).lower(stacked, batch).compile()
    coll = collective_bytes(compiled.as_text())
    ca = compiled.cost_analysis()
    rec = {"mesh": "2x16x16" if multi_pod else "16x16",
           "n_members": n_members,
           "collective_bytes": coll,
           "flops": float(ca.get("flops", 0))}
    if verbose:
        print(f"[ensemble-parallel] {rec['mesh']} x {n_members} members: "
              f"OK, collectives {coll}")
    return rec


if __name__ == "__main__":
    import os
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=512")
    dryrun_ensemble(multi_pod=True)
