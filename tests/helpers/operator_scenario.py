"""Subprocess helper: live controller end-to-end — priority shrink, expand on
completion, fault-tolerant restart from disk."""
import sys
import tempfile

import jax

from repro.checkpoint import DiskCheckpointStore
from repro.configs import smoke_config
from repro.core import (ElasticClusterController, ElasticTrainer, JobSpec,
                        JobStatus, PolicyConfig, TrainJobConfig)

devs = jax.devices()
assert len(devs) == 8
store = DiskCheckpointStore(tempfile.mkdtemp())


made = {}      # seed -> latest trainer (the controller drops completed ones)


def factory(steps, seed):
    def f(devices):
        made[seed] = ElasticTrainer(
            smoke_config("yi-6b"),
            TrainJobConfig(global_batch=8, seq_len=16, total_steps=steps,
                           seed=seed), devices)
        return made[seed]
    return f


# --- scenario 1: priority-driven shrink + expand-back -----------------------
op = ElasticClusterController(devs, slots=8,
                              policy=PolicyConfig(rescale_gap=0.0),
                              steps_per_tick=2)
op.submit(JobSpec("low", 1, 2, 8, 0.0, divides=8), factory(20, 0))
op.submit(JobSpec("high", 5, 4, 8, 0.001, divides=8), factory(8, 1))
m = op.run()
low = op.cluster.jobs["low"]
high = op.cluster.jobs["high"]
assert low.status == JobStatus.COMPLETED and high.status == JobStatus.COMPLETED
assert low.rescale_count >= 2, "low must shrink for high, then expand back"
shrinks = [(old, new) for _, jid, old, new, _ in op.rescale_events
           if jid == "low"]
assert shrinks[0][0] > shrinks[0][1], "first event is a shrink"
assert shrinks[-1][0] < shrinks[-1][1], "last event is an expand"
assert op.live["low"].trainer is None and op.live["high"].trainer is None
assert made[0].step_idx == 20
assert made[1].step_idx == 8
print("SCENARIO1 OK", m.row())

# --- scenario 2: node-failure -> restart from disk checkpoint ----------------
op2 = ElasticClusterController(devs, slots=8,
                               policy=PolicyConfig(rescale_gap=0.0),
                               disk_store=store, steps_per_tick=2)
op2.submit(JobSpec("victim", 3, 2, 4, 0.0, divides=8), factory(20, 5),
           checkpoint_every=4)
op2._process_submissions()
live = op2.live["victim"]
for _ in range(6):
    live.trainer.step()
live.trainer.save_disk(store, "victim")
op2.inject_failure("victim")
assert live.trainer is None, "process state must be lost on failure"
m2 = op2.run()
assert op2.cluster.jobs["victim"].status == JobStatus.COMPLETED
assert op2.live["victim"].failures == 1
assert made[5].step_idx == 20
print("SCENARIO2 OK", m2.row())
print("OK")
