"""Degraded reads of large objects through the plan's codec (mix driver).

Set-up lays the configured RS(n, k) code over the testbed's nodes (one
chunk per node, as a ``CodecPlan``), draws the configured batch of
objects from the run's seed in one compiled program, keeps their bytes
on the host, encodes them once (``encode_batch``) and warms every decode
shape the traffic can use. Each step of the window reads
the whole batch back with one node lost per object (drawn from the
seed): the surviving chunks are gathered on the device and decoded to
the host through ``CodecPlan.decode_requests``.

End to end: ``decode_gb_per_s``, object bytes returned over the window's
seconds. Check: every byte of a seeded sample of the window's batches
against the source (``wrong_bytes``, exact).
"""
from __future__ import annotations

import dataclasses

import numpy as np

from harness import Reservoir
from reference import codec as ref

MIB = 2**20


@dataclasses.dataclass
class State:
    plan: object
    n: int
    k: int
    fids: np.ndarray
    source: np.ndarray  # (B, k, chunk) uint8 on the host
    words: object  # (B, n, chunk) uint8 on the device
    pick: object
    loss_rng: object
    sample: Reservoir
    recording: bool = False


def _codec_plan(n: int, k: int, objects: int):
    """Every object an RS(n, k) codeword with chunk c on node c."""
    from repro.storage import CodecGroup, CodecPlan

    return CodecPlan(
        n=np.full(objects, n, np.int32), k=np.full(objects, k, np.int32),
        placement=np.ones((objects, n), bool),
        groups=(CodecGroup(n=n, k=k, file_ids=np.arange(objects)),),
    )


def setup(run) -> State:
    import jax
    import jax.numpy as jnp

    from repro.storage import decode_bank, encode_batch

    cfg = run.config["codec"]
    n, k, b = int(cfg["n"]), int(cfg["k"]), int(cfg["objects_per_batch"])
    plan = _codec_plan(n, k, b)
    chunk = -(-int(cfg["object_mib"]) * MIB // k)
    draw = jax.jit(jax.random.bits, static_argnums=(1, 2))
    seed = int(run.rng("objects").integers(0, 2**31))
    data = draw(jax.random.key(seed), (b, k, chunk), jnp.uint8)
    source = np.asarray(data)
    words = encode_batch(data, n)
    del data
    fids = np.arange(b)
    state = State(
        plan=plan, n=n, k=k, fids=fids, source=source, words=words,
        pick=jax.jit(lambda w, i, rows: w[i][rows]),
        loss_rng=run.rng("loss"), sample=Reservoir(int(run.mix["sampled_batches"]),
                                                   run.rng("sample")),
    )
    # every count of distinct erasure patterns a batch can hold (a lost
    # data chunk gives one of k patterns, a lost parity chunk the
    # systematic one) has its own decode-bank gather
    for p in range(1, min(b, k + 1) + 1):
        pats = [[c for c in range(k + 1) if c != j][:k] for j in range(p)]
        pats += [pats[0]] * (b - p)
        bank, idx = decode_bank(n, k, pats)
        jax.block_until_ready(bank[idx])
    _batch(run, state)
    state.recording = True
    return state


def _batch(run, state: State, patterns=None):
    """One batch of degraded reads; returns (decoded, patterns)."""
    import jax.numpy as jnp

    plan = state.plan
    with run.spans.span("batch"):
        lost = state.loss_rng.integers(0, state.n, size=len(state.fids))
        pats = patterns or [
            plan.degraded_patterns(int(f), [plan.chunk_nodes(int(f))[c]])
            for f, c in zip(state.fids, lost)
        ]
        with run.spans.span("gather"):
            chunks = [
                state.pick(state.words, i, jnp.asarray(
                    [c for c in range(state.n) if c != lost[i]][: state.k], jnp.int32))
                for i in range(len(state.fids))
            ]
        with run.spans.span("decode"):
            out = plan.decode_requests(list(state.fids), pats, chunks)
    return out, pats


def step(run) -> None:
    state = run.state
    out, _ = _batch(run, state)
    run.count("attempted", len(out))
    run.count("bytes", sum(o.nbytes for o in out))
    if state.recording:
        state.sample.offer(lambda: out)


def end_to_end(run) -> dict:
    return {"decode_gb_per_s": run.counters["bytes"] / run.window_s / 1e9}


def release(run) -> None:
    run.state.words = None


def check(run):
    state = run.state
    bad = failed = 0
    for out in state.sample.items:
        for i, o in enumerate(out):
            w = ref.wrong_bytes(o, state.source[i])
            bad += w
            failed += w > 0
    checks = [("wrong_bytes", float(bad), 0.0,
               "bytes of the sampled batches that differ from the source, at most")]
    if not state.sample.items:
        checks.append(("none_checked", 1.0, 0.0, "no batch came to be checked"))
        failed += 1
    return checks, int(failed)


def control(run):
    """Break the MDS guarantee's bookkeeping: decode the survivors of each
    loss as if the systematic rows had survived."""
    state = run.state
    out, _ = _batch(run, state, patterns=[list(range(state.k))] * len(state.fids))
    bad = sum(ref.wrong_bytes(o, state.source[i]) for i, o in enumerate(out))
    return [("wrong_bytes", float(bad), 0.0, "control: survivors decoded as the "
             "systematic rows")], int(bad > 0)
