"""A run with the timed path broken underneath comes out not correct: the
harness drives set-up, window and check at a tiny size on the CPU (the look
for a chip skipped) with one fault planted in the program, for each fault
the cell can have. The training cells have no exchange between chips to
leave out: every rank trains alone."""
import jax
import pytest

from chip.tests import tiny


def correct(run):
    return all(c.ok for c in tiny.drive(run))


def test_sound_runs_are_correct():
    assert correct(tiny.run("train", "production256-x8.train",
                            ranks_checked_per_chip=2))


def _unchanged_chunk(trainer, n_steps, lr_scale=1.0):
    import jax.numpy as jnp

    def chunk(params, opt, vols, key, step0, active, loss_ma):
        losses = jnp.zeros((n_steps, trainer.P), jnp.float32)
        return params, opt, active, loss_ma, jnp.ones_like(active), losses
    return chunk


def _half_batch(train_step_ref):
    def step(params, opt, coords, target, gate, *a, **kw):
        n = coords.shape[1] // 2
        return train_step_ref(params, opt, coords[:, :n], target[:, :n],
                              gate, *a, **kw)
    return step


def _loss_altered(train_step_ref):
    def step(*a, **kw):
        params, opt, loss = train_step_ref(*a, **kw)
        return params, opt, loss * 1.01
    return step


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
def test_train_faults_make_the_run_incorrect(monkeypatch, fault):
    from repro.core.trainer import DVNRTrainer
    from repro.kernels.fused_train_step import ref as step_ref

    if fault == "state_unchanged":
        monkeypatch.setattr(DVNRTrainer, "_chunk_fn", _unchanged_chunk)
    elif fault == "half_batch":
        monkeypatch.setattr(step_ref, "train_step_ref",
                            _half_batch(step_ref.train_step_ref))
    else:
        monkeypatch.setattr(step_ref, "train_step_ref",
                            _loss_altered(step_ref.train_step_ref))
    jax.clear_caches()
    try:
        assert not correct(tiny.run("train", "production256-x8.train",
                                    ranks_checked_per_chip=2))
    finally:
        monkeypatch.undo()
        jax.clear_caches()
