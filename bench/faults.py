"""Faults planted under the timed path, by name, for the checks' tests
and for the readings that set a limit's upper end on the chip
(``readings.py --fault <name>``). Each takes ``patch(owner, name,
value)``, which replaces one attribute of the program (``setattr``, or
pytest's ``monkeypatch.setattr``), and breaks one thing a cell's check
has to catch. The benchmark's own runs never plant one."""
from __future__ import annotations

import numpy as np


def fleet_latency_off(patch):
    """The largest latency of every simulated system 1 % too high."""
    import repro.storage as storage

    orig = storage.simulate_fleet

    def bad(*a, **kw):
        out = orig(*a, **kw)
        return out._replace(stream=out.stream._replace(maxv=out.stream.maxv * 1.01))

    patch(storage, "simulate_fleet", bad)


def fleet_half_left_out(patch):
    """Half of each call's chunks left out."""
    import repro.storage as storage

    orig = storage.simulate_fleet

    def bad(*a, n_chunks=1, **kw):
        return orig(*a, n_chunks=max(1, n_chunks // 2), **kw)

    patch(storage, "simulate_fleet", bad)


def fleet_sketch_dropped(patch):
    """The quantile sketch of every system and window left empty."""
    import repro.storage as storage

    orig = storage.simulate_fleet

    def bad(*a, **kw):
        out = orig(*a, **kw)
        return out._replace(stream=out.stream._replace(hist=0 * out.stream.hist),
                            windows=out.windows._replace(hist=0 * out.windows.hist))

    patch(storage, "simulate_fleet", bad)


def fleet_variance_off(patch):
    """The second moment of every system 1 % too high."""
    import repro.storage as storage

    orig = storage.simulate_fleet

    def bad(*a, **kw):
        out = orig(*a, **kw)
        return out._replace(stream=out.stream._replace(m2=out.stream.m2 * 1.01))

    patch(storage, "simulate_fleet", bad)


def replan_row_off(patch):
    """One entry of every candidate plan moved by 0.01."""
    from repro.serving import router

    orig = router.solve_batch

    def bad(probs, **kw):
        sols = orig(probs, **kw)
        return sols._replace(pi=sols.pi.at[:, 0, 0].add(0.01))

    patch(router, "solve_batch", bad)


def replan_state_unchanged(patch):
    """The incumbent plan deployed again, never replaced."""
    from repro.serving import AdaptiveReplanner

    orig = AdaptiveReplanner.replan

    def bad(self, *a, **kw):
        orig(self, *a, **kw)
        return np.asarray(kw["pi0"])

    patch(AdaptiveReplanner, "replan", bad)


def replan_solver_truncated(patch):
    """Every re-solve stopped after two iterations."""
    from repro.serving import router

    orig = router.solve_batch

    def bad(probs, **kw):
        return orig(probs, **dict(kw, max_iters=2))

    patch(router, "solve_batch", bad)


def replan_other_candidate(patch):
    """The arbitration deploys the candidate it scored highest."""
    import jax.numpy as jnp

    from repro.serving import router

    orig = router.batched_rollout_scores

    def bad(*a, **kw):
        scores, _ = orig(*a, **kw)
        return scores, jnp.argmax(jnp.where(jnp.isfinite(scores), scores, -jnp.inf))

    patch(router, "batched_rollout_scores", bad)


def codec_byte_flipped(patch):
    """One decoded byte flipped."""
    from repro.storage import CodecPlan

    orig = CodecPlan.decode_requests

    def bad(self, *a, **kw):
        out = [np.array(o) for o in orig(self, *a, **kw)]
        out[0][0, 0] ^= 1
        return out

    patch(CodecPlan, "decode_requests", bad)


def codec_half_left_out(patch):
    """Half of each batch left undecoded (returned as zeros)."""
    from repro.storage import CodecPlan

    orig = CodecPlan.decode_requests

    def bad(self, fids, pats, chunks, **kw):
        half = max(1, len(fids) // 2)
        out = orig(self, fids[:half], pats[:half], chunks[:half], **kw)
        return out + [np.zeros_like(out[0]) for _ in fids[half:]]

    patch(CodecPlan, "decode_requests", bad)


# the faults each cell's check is tested against at CPU sizes; a solve cut
# to two iterations leaves a plan off stationary only at the cell's own
# catalog (r = 1000), so it is read on the chip alone
FAULTS = {
    "replan.node-failure": [replan_row_off, replan_state_unchanged,
                            replan_other_candidate],
    "codec.degraded-read": [codec_byte_flipped, codec_half_left_out],
    "fleet.nj-client": [fleet_latency_off, fleet_half_left_out, fleet_sketch_dropped,
                        fleet_variance_off],
}
