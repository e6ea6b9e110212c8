"""Faults planted in the program under the harness, one for each kind a
training cell can have (a run with any of them is not correct):

  unchanged    the round returns its model state unchanged
  half_batch   each client trains on half its batch, its loss the mean
               over the rest
  no_exchange  (mesh) the region merge across chips left out: the global
               model is the first RSU's
"""
import jax

from repro.core import aggregation, engine, hierarchical


def plant(monkeypatch, fault: str):
    engine.reset_engine_caches()
    if fault == "unchanged":
        orig = engine._state_of

        def state_of(carry, state, *args):
            new = orig(carry, state, *args)
            topo = new.topo
            if "rsu_models" in (topo or {}):
                topo = {**topo, "rsu_models": state.topo["rsu_models"]}
            return new.replace(global_tree=state.global_tree, topo=topo,
                               comms=state.comms)
        monkeypatch.setattr(engine, "_state_of", state_of)
    elif fault == "half_batch":
        orig = engine.raw_local_step

        def raw_local_step(cfg):
            step = orig(cfg)
            return lambda tree, images, key, lr: step(
                tree, images[: images.shape[0] // 2], key, lr)
        monkeypatch.setattr(engine, "raw_local_step", raw_local_step)
    elif fault == "no_exchange":
        def sharded_hierarchical(trees, blur, mesh, n_rsus, **kw):
            rows = blur.shape[0] // n_rsus
            return aggregation._weighted_stacked_sum(
                jax.tree.map(lambda x: x[:rows], trees),
                aggregation.flsimco_weights(blur[:rows]))
        monkeypatch.setattr(hierarchical, "sharded_hierarchical",
                            sharded_hierarchical)
    else:
        raise ValueError(fault)
