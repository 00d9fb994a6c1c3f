"""Faults planted under the rack cell's timed path, by name, for its
check's tests and for the readings that set its limits' upper ends on the
chip (``rack_readings.py --fault <name>``). Each takes ``patch(owner,
name, value)`` as those of ``faults.py`` do, and breaks one thing the
rack cell's check has to catch: every candidate plan's first row is
changed where the batched solve returns it. The benchmark's own runs
never plant one."""
from __future__ import annotations

import numpy as np

import faults


def _first_rows(patch, change):
    """Replace row 0 of every candidate plan by ``change(row, prob)``."""
    import jax.numpy as jnp

    from repro.serving import router

    orig = router.solve_batch

    def bad(probs, **kw):
        sols = orig(probs, **kw)
        pi = np.array(sols.pi)
        for b in range(pi.shape[0]):
            pi[b, 0] = change(pi[b, 0].copy(), probs[0])
        return sols._replace(pi=jnp.asarray(pi))

    patch(router, "solve_batch", bad)


def _hosts_per_rack(prob) -> int:
    dom = np.asarray(prob.domain)
    return dom.size // (int(dom.max()) + 1)


def replan_two_hosts_in_a_rack(patch):
    """Half of the first row's largest entry moved onto the next host of
    its rack: row and rack sums kept, two chunks of a stripe in one rack."""

    def change(row, prob):
        h = _hosts_per_rack(prob)
        j = int(np.argmax(row))
        row[j - j % h + (j % h + 1) % h] += row[j] / 2
        row[j] /= 2
        return row

    _first_rows(patch, change)


def replan_rack_over_cap(patch):
    """Half a unit of the first row moved from its smallest placed rack
    onto a second host of its fullest rack: that rack holds 1.5."""

    def change(row, prob):
        h = _hosts_per_rack(prob)
        sums = row.reshape(-1, h).sum(-1)
        full = int(np.argmax(sums))
        placed = np.where(sums > 0.5)[0]
        donor = placed[np.argmin(sums[placed])]
        row[donor * h: (donor + 1) * h] *= 1 - 0.5 / sums[donor]
        j = full * h + int(np.argmin(row[full * h: (full + 1) * h]))
        row[j] += 0.5
        return row

    _first_rows(patch, change)


def replan_mass_on_down_rack(patch):
    """0.01 of the first row's largest entry moved onto a host that is down
    (when one is)."""

    def change(row, prob):
        dead = np.where(~np.asarray(prob.mask, bool)[0])[0]
        if dead.size:
            j = int(np.argmax(row))
            row[j] -= 0.01
            row[dead[0]] += 0.01
        return row

    _first_rows(patch, change)


RACK_FAULTS = [replan_two_hosts_in_a_rack, replan_rack_over_cap, replan_mass_on_down_rack]

# the faults the rack cell's check is tested against at CPU sizes: its own
# and the closed loop's of faults.py
FAULTS = {
    "replan.f4-rack-failure": RACK_FAULTS + [faults.replan_state_unchanged,
                                             faults.replan_other_candidate,
                                             faults.replan_solver_truncated],
}
