"""Faults planted in the program underneath a run, one for each fault a
cell can have.  Each takes ``mp``, anything with pytest's
``mp.setattr(obj, name, value)``, and replaces one function of the
program.  The tests plant them at a test's size; ``control.py --fault``
plants one at a cell's own size on the chip.
"""

from __future__ import annotations

import numpy as np


def state_unchanged(mp):
    """Every Adam step of the tuner returns the state it was given."""
    import jax.numpy as jnp
    import repro.core._opt as opt
    orig = opt.adam_update

    def frozen(grad, state, lr, **kw):
        delta, st = orig(grad, state, lr, **kw)
        return jnp.zeros_like(delta), st
    mp.setattr(opt, "adam_update", frozen)


def half_batch(mp):
    """The grid solves the first half of its problems only and hands their
    answers out for the rest."""
    import jax
    import jax.numpy as jnp
    import repro.core.batch as batch
    orig = batch._solve_many

    def half(key, W, rhos, *a, **kw):
        P = W.shape[0]
        h = max(P // 2, 1)
        out = orig(key, W[:h], rhos[:h], *a, **kw)
        idx = jnp.arange(P) % h
        return jax.tree_util.tree_map(lambda x: x[idx], out)
    mp.setattr(batch, "_solve_many", half)


def answer_altered(mp):
    """The tunings' compaction policies are flipped (leveling <-> tiering)
    after they were scored."""
    import repro.core.batch as batch
    from repro.core import Phi
    orig = batch._build_results

    def altered(out, design, sys):
        res = orig(out, design, sys)
        for r in res:
            K = np.asarray(r.phi.K, np.float32)
            flip = np.where(K > 1.0, 1.0, np.maximum(r.phi.T - 1.0, 1.0))
            r.phi = Phi(T=r.phi.T, mfilt_bits=r.phi.mfilt_bits,
                        K=flip.astype(np.float32))
        return res
    mp.setattr(batch, "_build_results", altered)


def writes_dropped(mp):
    """Updates are acknowledged and counted but never stored."""
    from repro.lsm.engine import LSMTree

    def put_batch(self, keys, values):
        self.stats.queries["w"] += len(keys)
    mp.setattr(LSMTree, "put_batch", put_batch)


def half_session(mp):
    """Each request executes the first half of its operations only."""
    import repro.lsm as lsm
    orig = lsm.execute_session

    def half(tree, plan, **kw):
        n = plan.n_queries // 2
        k = plan.kinds[:n]
        cut = lsm.SessionPlan(
            workload=plan.workload, kinds=k,
            point_keys=plan.point_keys[:int((k <= 1).sum())],
            range_los=plan.range_los, range_his=plan.range_his,
            write_keys=plan.write_keys[:int((k == 3).sum())])
        return orig(tree, cut, **kw)
    mp.setattr(lsm, "execute_session", half)


def read_altered(mp):
    """The first key of each read batch comes back not found."""
    from repro.lsm.engine import LSMTree
    orig = LSMTree._lookup_batch

    def altered(self, keys_arr, *a, **kw):
        found, enc = orig(self, keys_arr, *a, **kw)
        if len(found):
            found[0] = False
        return found, enc
    mp.setattr(LSMTree, "_lookup_batch", altered)


TUNE = {"state_unchanged": state_unchanged, "half_batch": half_batch,
        "answer_altered": answer_altered}
SERVE = {"writes_dropped": writes_dropped, "half_session": half_session,
         "read_altered": read_altered}
