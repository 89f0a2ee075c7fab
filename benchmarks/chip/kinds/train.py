"""Closed-loop in situ training: every rank trains its own INR on its own
box of the field, with no communication, through the scan-fused chunk
program of ``repro.api.train``'s trainer, one fixed chunk of steps per call
and the chunk's loss trace read back after each (as ``DVNRTrainer.train``
does at every ``check_every``), a chunk late.

Every dot the program traces and runs is at the matmul precision the
configuration states (``matmul_precision``): on a TPU, JAX's default runs a
float32 dot as one bfloat16 pass, which is not the float32 the
configuration states.

Traffic keys: ``chunk_steps`` (steps per call; the check follows the first
chunk), ``ranks_checked_per_chip`` (ranks of each chip the reference
follows, drawn from the seed), ``time_range`` (the simulation time of the
field, drawn from the seed), ``table_range`` (hash-table init bound).

Set-up builds the trainer and its state through ``api.train`` (no steps),
then drives that same trainer through its first chunk with the window's own
call, keeping what the check reads of each checked rank: its loss at every
step of the chunk, and its parameters and Adam first moment after the
chunk; then through one more chunk, which warms up the call on a chunk's
output. The window then calls on until ``--seconds`` have passed, and ends
with the chunk that is in flight at that time.
"""
from __future__ import annotations

import sys
import time
from dataclasses import dataclass

import numpy as np

from chip import harness, inputs, work
from chip.harness import Check, span


@dataclass(frozen=True)
class Part:
    """What ``api.train`` reads of a rank's partition besides its volume."""

    origin: tuple
    extent: tuple
    ghost: int
    owned_shape: tuple
    vmin: float
    vmax: float


def _host(tree, ranks):
    import jax
    return jax.tree.map(lambda x: np.asarray(x)[np.asarray(ranks)], tree)


def checked_ranks(run: harness.Run, rng) -> list[int]:
    P, n_chips = run.cell.config["ranks"], len(run.devices)
    per = P // n_chips
    k = min(per, run.cell.traffic["ranks_checked_per_chip"])
    return sorted(int(c * per + r) for c in range(n_chips)
                  for r in rng.choice(per, size=k, replace=False))


def setup(run: harness.Run) -> None:
    import jax
    import jax.numpy as jnp
    from repro import api
    from repro.configs.dvnr import DVNRConfig

    config, traffic = run.cell.config, run.cell.traffic
    model, P = config["model"], config["ranks"]
    d = inputs.draws(run.seed)
    t = float(d.rng.uniform(*traffic["time_range"]))
    ranks = checked_ranks(run, d.rng)
    with span("bench.inputs"):
        vols, ranges = inputs.make_volumes(config, t, run.mesh)
        w0 = inputs.make_weights(model, P, d.weights_key,
                                 traffic["table_range"])
        jax.block_until_ready((vols, w0))
    g, n = config["ghost"], config["local"]
    parts = [Part(o, e, g, (n, n, n), float(lo), float(hi))
             for (o, e), (lo, hi) in zip(inputs.boxes(P), ranges)]
    key = jnp.asarray(d.key, jnp.uint32)
    precision = config["matmul_precision"]
    with span("bench.build"), jax.default_matmul_precision(precision):
        _, info = api.train(parts, DVNRConfig(**model), backend="auto",
                            mesh=run.mesh, steps=0, key=key, cached_params=w0,
                            volumes=vols, **run.program)
    trainer, state = info["trainer"], info["state"]
    chunk = traffic["chunk_steps"]

    def call(state):
        """Dispatch one chunk; its loss trace stays on the device."""
        with span("bench.train_call"), jax.default_matmul_precision(precision):
            return trainer.train_chunk(state, vols, chunk, key=key)

    def read(losses):
        with span("bench.read_losses"):
            return np.asarray(losses)                   # (chunk, P)

    # the check's chunk, through the window's own call
    state, losses = call(state)
    losses = read(losses)
    run.state.update(
        call=call, read=read, vols=vols, key=d.key, ranks=ranks,
        w0=_host(w0, ranks), prog_losses=losses[:, ranks].T,
        prog_m=_host(state.opt["m"], ranks),
        prog_params=_host(state.params, ranks))
    # One more chunk, from a chunk's output as every window call is: on a
    # mesh the chunk's output is placed otherwise than api.train's state,
    # and the first call on it compiles.
    state, losses = call(state)
    read(losses)
    run.state["train_state"] = state


def window(run: harness.Run) -> None:
    """Chunks back to back, each dispatched before the loss trace of the one
    before it is read, so that the chip has a chunk queued while the host
    reads (or stalls). Once ``--seconds`` have passed at a read, nothing
    more is sent: the chunk in flight is waited for and counted, and the
    clock is read after it."""
    import jax

    state = run.state.pop("train_state")
    call, read = run.state["call"], run.state["read"]
    losses, reads = [], []
    t0 = time.perf_counter()
    state, pending = call(state)
    while True:
        state, ahead = call(state)
        losses.append(read(pending))
        pending = ahead
        reads.append(time.perf_counter() - t0)
        if reads[-1] >= run.seconds:
            break
    losses.append(read(pending))
    jax.block_until_ready((state.params, state.opt))
    elapsed = time.perf_counter() - t0
    print(f"window: chunk reads at {reads + [elapsed]} s", file=sys.stderr)
    config = run.cell.config
    steps = sum(len(x) for x in losses)
    samples = steps * config["ranks"] * config["model"]["batch_size"]
    run.window.update(
        seconds=elapsed, steps=steps, samples=samples, attempted=steps,
        failed=int(sum(not np.isfinite(x).all() for x in losses)),
        nonfinite_ranks=int(np.sum(~np.asarray(state.finite))),
        step_work=work.train_step_work(config["model"], config["ranks"]),
        metrics={"train_samples_per_s": samples / elapsed})


def release(run: harness.Run) -> None:
    """Drop the program's state before the reference runs."""
    for k in ("call", "read", "train_state"):
        run.state.pop(k, None)


def reference(run: harness.Run, batch_fraction: float = 1.0) -> dict:
    """The plain reference of the checked ranks' first chunk."""
    import jax
    from chip.reference import dvnr as ref

    config, s = run.cell.config, run.state
    steps = run.cell.traffic["chunk_steps"]
    dev = run.devices[0]
    out = {"losses": [], "grad1": [], "m": [], "params": []}
    for i, r in enumerate(s["ranks"]):
        w0 = jax.device_put(jax.tree.map(lambda x: x[i], s["w0"]), dev)
        vol = jax.device_put(s["vols"][r], dev)
        res = ref.train(config["model"], w0, vol, s["key"], r, steps,
                        config["ghost"], batch_fraction)
        for k in out:
            out[k].append(jax.tree.map(np.asarray, res[k]))
    return out


def _norm_gap(prog_leaves, ref_leaves, keep) -> float:
    """Worst leaf's |‖prog‖ − ‖ref‖| over the larger of that leaf's
    reference norm and the median leaf's, over the leaves ``keep`` marks."""
    pn = np.asarray([np.linalg.norm(np.asarray(x, np.float64))
                     for x in prog_leaves])
    rn = np.asarray([np.linalg.norm(np.asarray(x, np.float64))
                     for x in ref_leaves])
    scale = np.maximum(rn, np.median(rn))
    gaps = np.abs(pn - rn) / np.where(scale > 0, scale, 1.0)
    return float(gaps[keep].max()) if keep.any() else 0.0


def compare(run: harness.Run, refs: dict) -> dict:
    """The gaps the check holds to its limits, worst over checked ranks:
    every step's loss (relative), and after the chunk the Adam first moment
    and the parameters' change (the worst leaf's gap of norms). The change
    leaves out leaves whose first reference gradient is under a thousandth
    of the median leaf's: they move by round-off alone."""
    import jax

    s = run.state
    loss, moment, change = 0.0, 0.0, 0.0
    for i in range(len(s["ranks"])):
        def leaves(tree):
            return jax.tree.leaves(jax.tree.map(lambda x: x[i], tree))

        rl = np.asarray(refs["losses"][i], np.float64)
        pl = np.asarray(s["prog_losses"][i], np.float64)
        loss = max(loss, float(np.max(np.abs(pl - rl) / np.abs(rl))))
        gn = np.asarray([np.linalg.norm(g)
                         for g in jax.tree.leaves(refs["grad1"][i])])
        moved = gn >= 1e-3 * np.median(gn)
        moment = max(moment, _norm_gap(leaves(s["prog_m"]),
                                       jax.tree.leaves(refs["m"][i]),
                                       np.ones_like(moved)))
        w0 = leaves(s["w0"])
        prog_d = [p - w for p, w in zip(leaves(s["prog_params"]), w0)]
        ref_d = [p - w for p, w in zip(jax.tree.leaves(refs["params"][i]), w0)]
        change = max(change, _norm_gap(prog_d, ref_d, moved))
    return {"loss_gap": loss, "moment_gap": moment, "change_gap": change}


def check(run: harness.Run) -> list[Check]:
    gaps = compare(run, reference(run))
    gaps["nonfinite_ranks"] = float(run.window["nonfinite_ranks"])
    return [Check(k, v, float(run.cell.limits[k])) for k, v in gaps.items()]
