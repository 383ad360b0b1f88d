"""What every driver shares: the ``Run`` it hands back, the seeds it draws
from ``--seed``, and the comparisons of rewards with the reference.

A driver is a file ``chipbench/drivers/<driver>.py`` that a traffic file
names in its ``driver`` key. It has one function,

    run(config, traffic, *, seed, seconds, window, devices) -> Run

which builds its inputs from ``seed``, warms up every shape the window
uses (set-up), calls ``window.open()``, measures for ``seconds``, calls
``window.close(t_last)`` at the last completion, and returns a ``Run``.
The driver brings the reference comparison it is checked by: ``Run.check``.
"""
from __future__ import annotations

import dataclasses
import gc

import numpy as np

import reference

ALGORITHMS = ("ogasched",) + reference.HEURISTICS


def derived_seeds(seed: int, n: int, stream: int = 0) -> np.ndarray:
    """``n`` uint32 seeds drawn from ``--seed`` (one stream per use)."""
    ss = np.random.SeedSequence(seed % (1 << 64)).spawn(stream + 1)[stream]
    return ss.generate_state(n, dtype=np.uint32)


def sample_rng(seed: int) -> np.random.Generator:
    """The generator that picks which answers are compared."""
    return np.random.default_rng(
        np.random.SeedSequence(seed % (1 << 64)).spawn(8)[7])


def trace_config(config: dict, **overrides):
    """The program's TraceConfig for a deployment file's trace fields."""
    from repro.sched import trace
    names = {f.name for f in dataclasses.fields(trace.TraceConfig)}
    fields = {k: tuple(v) if isinstance(v, list) else v
              for k, v in config.items() if k in names}
    return trace.TraceConfig(**{**fields, **overrides})


@dataclasses.dataclass
class Run:
    end_to_end: dict  # every end-to-end metric but setup_s, which the harness takes
    stats: dict  # counters the per-layer readers read
    attempted: int
    failed: int
    check: callable  # check(control=False) -> {name: number}
    release: callable = gc.collect  # drop the program's state before check
    info: dict = dataclasses.field(default_factory=dict)  # what check saw


def rel_gap(got, want, floor=1.0) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want) / np.maximum(np.abs(want), floor)))


def reward_gaps(prog: dict, prog_avg: dict, prog_gain: dict, ref: dict,
                untied: np.ndarray, info: dict | None = None) -> dict:
    """Per-deployment rewards of the program against the reference's.

    prog, ref: {algorithm: (S, T) per-slot rewards}; prog_avg: the
    program's own summary averages {algorithm: (S,)}; prog_gain: its own
    improvement of OGASched over each heuristic, in percent {heuristic:
    (S,)}; untied: (S,) how many leading slots of OGASched's rewards do not
    depend on which side of a k* near-tie was taken (reference.TIE).

    oga_slot_gap: OGASched's widest per-slot gap over those slots, over
    the deployment's mean reward magnitude. avg_gap: the widest relative
    gap of an average reward: a heuristic's over every slot, OGASched's
    over those slots. summary_gap: the widest relative gap of the program's
    summary (each average, and each improvement as a fraction) from the
    plain reduction of its own per-slot rewards."""
    want = {n: np.asarray(v, np.float64) for n, v in ref.items()}
    got = {n: np.asarray(prog[n], np.float64) for n in want}
    keep = np.arange(want["ogasched"].shape[1])[None] < untied[:, None]
    scale = np.maximum(np.mean(np.abs(want["ogasched"]), axis=1), 1.0)
    slot = np.max(np.abs(got["ogasched"] - want["ogasched"]) * keep,
                  axis=1) / scale
    prefix = lambda v: np.sum(v * keep, axis=1) / untied
    avg = max([rel_gap(prefix(got["ogasched"]), prefix(want["ogasched"]))]
              + [rel_gap(got[n].mean(axis=1), want[n].mean(axis=1))
                 for n in want if n != "ogasched"])
    if info is not None:
        info["oga_slots_compared"] = float(np.mean(untied / keep.shape[1]))
    plain = {n: v.mean(axis=1) for n, v in got.items()}
    summary = max(
        [rel_gap(prog_avg[n], plain[n]) for n in want]
        + [float(np.max(np.abs(
            np.asarray(prog_gain[n], np.float64)
            - reference.improvement_pct(plain["ogasched"], plain[n])))) / 100
           for n in want if n != "ogasched"])
    return {"oga_slot_gap": float(np.max(slot)), "avg_gap": avg,
            "summary_gap": summary}


def bf16(x) -> np.ndarray:
    import jax.numpy as jnp
    return np.asarray(jnp.asarray(x, jnp.bfloat16).astype(jnp.float32))


def reference_rewards(spec, arrivals, eta0, decay, control: bool):
    """({algorithm: (S, T) rewards}, untied (S,)) of the reference in
    float32, or in bfloat16 for the control: untied counts the leading
    slots before the first k* near-tie has an effect."""
    import jax.numpy as jnp
    dtype = jnp.bfloat16 if control else jnp.float32
    out, tied = reference.rewards_batch(
        spec, arrivals, jnp.asarray(eta0, jnp.float32),
        jnp.asarray(decay, jnp.float32), algorithms=ALGORITHMS, dtype=dtype)
    tied = np.asarray(tied)
    untied = np.where(tied.any(axis=1), np.argmax(tied, axis=1) + 1,
                      tied.shape[1])
    return {n: np.asarray(v) for n, v in out.items()}, untied


def control_gaps(spec, arrivals, eta0, decay, ref: dict,
                 untied: np.ndarray, info: dict | None = None) -> dict:
    """reward_gaps of the control put in the program's place: the
    reference's rewards and their summary, computed in bfloat16."""
    prog, _ = reference_rewards(spec, arrivals, eta0, decay, control=True)
    avg = {n: bf16(np.mean(bf16(v), axis=1)) for n, v in prog.items()}
    gain = {n: bf16(reference.improvement_pct(avg["ogasched"], avg[n]))
            for n in reference.HEURISTICS}
    return reward_gaps(prog, avg, gain, ref, untied, info)


def load_driver(name: str):
    """The module of ``drivers/<name>.py``, for a driver that builds on it."""
    import os

    import harness
    return harness.load_module(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "drivers", f"{name}.py"))
