"""The paper's central systems claim: DVNR training requires NO inter-process
communication. We compile the distributed (shard_map) train step AND the
scan-fused multi-step chunk on 8 fake devices in a subprocess and run the
``zero_collectives`` static check from :mod:`repro.analysis` over the post-SPMD
HLO of both — a structured opcode walk, not a regex scrape. A deliberately
communicating control program (a ppermute ring shift under shard_map) must FAIL
the same check, so a vacuous walk cannot pass silently.
"""
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import build_mesh
    from repro.configs import dvnr as dvnr_cfg
    from repro.core.sampling import step_keys
    from repro.core.trainer import DVNRTrainer
    from repro.data.volume import make_partition
    from repro.analysis import CheckContext, capture, run_checks

    mesh = build_mesh(np.asarray(jax.devices()).reshape(4, 2), ("data", "model"))
    cfg = dvnr_cfg.SMOKE.replace(batch_size=256)
    n_parts = 8
    parts = [make_partition("s3d", p, (2, 2, 2), (8, 8, 8))
             for p in range(n_parts)]
    vols = jnp.stack([p.normalized() for p in parts])
    tr = DVNRTrainer(cfg, n_partitions=n_parts, mesh=mesh)
    state = tr.init(jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(1)
    keys = step_keys(key, 0, n_parts)
    ctx = CheckContext(backend=tr.backend)

    step = capture(tr._step_fn, state.params, state.opt, vols, keys,
                   state.active, state.loss_ma, name="step")
    chunk = capture(tr._chunk_fn(5), state.params, state.opt, vols, key,
                    jnp.int32(0), state.active, state.loss_ma, name="chunk")
    for prog in (step, chunk):
        rep = run_checks(prog, ctx, checks=["zero_collectives"])
        res = rep.result("zero_collectives")
        n_ops = int(res.details["note"].split()[0])  # "N HLO ops walked"
        print(f"{prog.name.upper()}_CLEAN:", int(rep.passed and n_ops > 0))

    # control: a ppermute ring shift through the same mesh MUST be flagged —
    # proves the walk actually sees post-SPMD collectives, not an empty module
    ring = [(i, (i + 1) % n_parts) for i in range(n_parts)]
    shift = jax.jit(shard_map(
        lambda v: jax.lax.ppermute(v, ("data", "model"), perm=ring),
        mesh=mesh, in_specs=P(("data", "model")),
        out_specs=P(("data", "model"))))
    control = run_checks(capture(shift, vols, name="ring"), ctx,
                         checks=["zero_collectives"])
    print("CONTROL_DIRTY:", int(not control.passed))

    # also verify the chunk actually runs and decreases loss on all 8 devices
    state, trace = tr.train_chunk(state, vols, 20, key=key)
    print("LOSS:", float(trace[-1].mean()))
""")


def test_distributed_train_step_has_no_collectives():
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, timeout=420)
    assert r.returncode == 0, r.stdout + r.stderr
    lines = dict(l.split(": ") for l in r.stdout.strip().splitlines()
                 if ": " in l)
    assert int(lines["STEP_CLEAN"]) == 1, r.stdout
    assert int(lines["CHUNK_CLEAN"]) == 1, r.stdout
    assert int(lines["CONTROL_DIRTY"]) == 1, r.stdout
    assert float(lines["LOSS"]) < 0.5
