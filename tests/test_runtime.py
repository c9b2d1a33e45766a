"""Training runtime: fault retry, resume, stragglers, elastic DP."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import registry as R
from repro.optim import AdamWConfig
from repro.runtime.train import FaultInjector, TrainConfig, Trainer


def _api():
    return R.build("smollm-135m", smoke=True)


def _cfg(**kw):
    base = dict(seq_len=32, global_batch=4, steps=6,
                optim=AdamWConfig(warmup_steps=2, total_steps=6))
    base.update(kw)
    return TrainConfig(**base)


class TestTraining:
    def test_loss_decreases(self):
        tr = Trainer(_api(), _cfg(steps=12,
                                  optim=AdamWConfig(peak_lr=5e-3,
                                                    warmup_steps=2,
                                                    total_steps=12)))
        _, _, hist = tr.run()
        first = np.mean([h["loss"] for h in hist[:3]])
        last = np.mean([h["loss"] for h in hist[-3:]])
        assert last < first

    def test_transient_fault_retried(self):
        tr = Trainer(_api(), _cfg(),
                     fault_injector=FaultInjector(fail_steps=(2,)))
        _, _, hist = tr.run()
        assert tr.retried_steps == [2]
        assert len(hist) == 6            # no step lost

    def test_straggler_detected(self):
        tr = Trainer(_api(), _cfg(steps=10, straggler_factor=2.0),
                     fault_injector=FaultInjector(slow_steps=(7,),
                                                  slow_s=1.0))
        tr.run()
        assert 7 in tr.straggler_steps

    def test_checkpoint_resume_identical(self, tmp_path):
        """train(10) == train(5) + resume(5..10), bit-for-bit params."""
        opt = AdamWConfig(peak_lr=1e-3, warmup_steps=2, total_steps=10,
                          grad_dtype=jnp.float32)
        straight = Trainer(_api(), _cfg(steps=10, optim=opt))
        p_straight, _, _ = straight.run()

        d = str(tmp_path / "ck")
        part1 = Trainer(_api(), _cfg(steps=5, optim=opt, ckpt_dir=d,
                                     ckpt_every=100))
        part1.run()                       # final save at step 5
        part2 = Trainer(_api(), _cfg(steps=10, optim=opt, ckpt_dir=d,
                                      ckpt_every=100))
        (params, opt_state), start = part2.restore()
        assert start == 5
        p_resumed, _, _ = part2.run(params, opt_state, start)
        for a, b in zip(jax.tree.leaves(p_straight),
                        jax.tree.leaves(p_resumed)):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))

    def test_unrecoverable_fault_rolls_back(self, tmp_path):
        d = str(tmp_path / "ck")
        tr = Trainer(_api(), _cfg(steps=8, ckpt_dir=d, ckpt_every=2,
                                  max_retries=1),
                     fault_injector=FaultInjector(
                         fail_steps=(5,), max_failures_per_step=5))
        _, _, hist = tr.run()
        # rollback happened (step 5 failed twice -> restore at 4)
        assert len(tr.retried_steps) >= 2
        assert hist[-1]["step"] == 7


class TestElasticResume:
    def test_dp_resize_preserves_stream(self):
        """dp=1 rank-0 batches == concat of dp=2 rank batches."""
        from repro.data import DataConfig, make_batch
        cfg = DataConfig(vocab=100, seq_len=16, global_batch=4)
        full = make_batch(cfg, step=3, dp_rank=0, dp_size=1)
        halves = [make_batch(cfg, step=3, dp_rank=r, dp_size=2)
                  for r in range(2)]
        np.testing.assert_array_equal(
            full["tokens"],
            np.concatenate([h["tokens"] for h in halves]))

