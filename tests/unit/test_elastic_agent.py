"""Elastic agent integration test (VERDICT round-2 missing #4).

Reference analogue: ``DSElasticAgent`` restart-on-membership-change
(``deepspeed/elasticity/elastic_agent.py:32``). The test runs the real
supervisor loop against a real training subprocess on the virtual CPU mesh:
train at world=2, flip membership to world=4 mid-run, and assert the agent
kills + relaunches with the re-solved (micro, gas) decomposition and that
training RESUMES from the universal checkpoint (step counter and loss
continue, no restart from scratch).
"""

import json
import os
import sys
import threading
import time

import numpy as np
import pytest

from deepspeed_tpu.elasticity import ElasticAgent
from deepspeed_tpu.elasticity.elastic_agent import _world_from_hostfile

TARGET_STEPS = 10

CHILD = r'''
import json, os, sys, time
sys.path.insert(0, {repo!r})
world = int(os.environ["DSTPU_WORLD_SIZE"])
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={{world}}"
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", world)
import numpy as np
import deepspeed_tpu

cfg = json.load(open(sys.argv[1]))
cfg["mesh"] = {{"data": world}}
cfg["steps_per_print"] = 10**9

import jax.numpy as jnp

def loss_fn(params, batch):
    pred = jnp.tanh(batch["x"] @ params["w1"]) @ params["w2"]
    return jnp.mean((pred - batch["y"]) ** 2)

rngs = np.random.default_rng(0)
params = {{
    "w1": jnp.asarray(rngs.normal(size=(16, 32)) * 0.3, jnp.float32),
    "w2": jnp.asarray(rngs.normal(size=(32, 4)) * 0.3, jnp.float32),
}}
engine, _, _, _ = deepspeed_tpu.initialize(
    model=loss_fn, model_parameters=params, config=cfg)
engine.load_checkpoint({ckpt!r})  # None on the first incarnation

data = np.random.default_rng(1)
bsz = cfg["train_batch_size"]
gas = cfg["gradient_accumulation_steps"]
log = open({log!r}, "a")
while engine.global_steps < {target}:
    x = data.normal(size=(bsz, 16)).astype(np.float32)
    y = (x[:, :4] * 0.5).astype(np.float32)
    loss = float(engine.train_batch(batch={{"x": x, "y": y}}))
    print(json.dumps({{"step": engine.global_steps, "loss": loss, "world": world,
                      "micro": cfg["train_micro_batch_size_per_gpu"], "gas": gas}}),
          file=log, flush=True)
    engine.save_checkpoint({ckpt!r}, tag=f"step{{engine.global_steps}}")
    time.sleep(0.4)  # give the agent's poll a window mid-run
print("child done at", engine.global_steps)
'''


@pytest.fixture
def elastic_setup(tmp_path):
    ds_config = {
        "train_batch_size": 16,
        "optimizer": {"type": "AdamW", "params": {"lr": 5e-2}},
        "zero_optimization": {"stage": 1},
        "elasticity": {
            "enabled": True,
            "max_train_batch_size": 16,
            "micro_batch_sizes": [2, 4],
            "min_gpus": 1,
            "max_gpus": 8,
            "min_time": 0,
            "version": 0.1,
        },
    }
    ckpt = str(tmp_path / "ckpt")
    log = str(tmp_path / "losses.jsonl")
    script = tmp_path / "train_child.py"
    repo = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    script.write_text(CHILD.format(repo=repo, ckpt=ckpt, log=log, target=TARGET_STEPS))
    return ds_config, str(script), ckpt, log, tmp_path


def _read_log(log):
    if not os.path.exists(log):
        return []
    return [json.loads(l) for l in open(log) if l.strip()]


def test_membership_change_resumes_from_checkpoint(elastic_setup):
    ds_config, script, ckpt, log, tmp_path = elastic_setup
    world_file = tmp_path / "world"
    world_file.write_text("2")
    env_clean = {k: v for k, v in os.environ.items() if not k.startswith(("XLA_", "JAX_"))}

    agent = ElasticAgent(
        [sys.executable, script, "{config}"],
        ds_config,
        world_file=str(world_file),
        poll_interval=0.2,
        max_restarts=3,
        workdir=str(tmp_path / "agent"),
    )
    rc = {}
    # the agent blocks; membership flips from the test thread mid-run
    t = threading.Thread(target=lambda: rc.update(code=agent.run()), daemon=True)
    old_env = dict(os.environ)
    os.environ.clear()
    os.environ.update(env_clean)
    try:
        t.start()
        deadline = time.time() + 300
        while time.time() < deadline:
            entries = _read_log(log)
            if len(entries) >= 3:
                break
            time.sleep(0.5)
        assert len(_read_log(log)) >= 3, "first incarnation never trained"
        world_file.write_text("4")  # membership change: 2 -> 4 workers
        t.join(timeout=300)
        assert not t.is_alive(), "agent did not finish"
    finally:
        os.environ.clear()
        os.environ.update(old_env)

    assert rc.get("code") == 0
    entries = _read_log(log)
    # two incarnations with the re-solved decomposition
    assert agent.restarts >= 1
    assert len(agent.launches) >= 2
    assert agent.launches[0]["world"] == 2 and agent.launches[-1]["world"] == 4
    p0, p1 = agent.launches[0]["plan"], agent.launches[-1]["plan"]
    assert p0["train_batch_size"] == p1["train_batch_size"] == 16  # batch invariant
    assert (
        p0["train_micro_batch_size_per_gpu"] * p0["gradient_accumulation_steps"] * 2
        == p1["train_micro_batch_size_per_gpu"] * p1["gradient_accumulation_steps"] * 4
        == 16
    )
    # training RESUMED: the step counter continues across the restart and
    # reaches the target; the post-restart loss is below the initial loss
    worlds = [e["world"] for e in entries]
    assert 2 in worlds and 4 in worlds
    steps_w4 = [e["step"] for e in entries if e["world"] == 4]
    max_w2 = max(e["step"] for e in entries if e["world"] == 2)
    assert min(steps_w4) > 1 and min(steps_w4) <= max_w2 + 1, (max_w2, steps_w4)
    assert max(steps_w4) == TARGET_STEPS
    first_loss = entries[0]["loss"]
    resumed_losses = [e["loss"] for e in entries if e["world"] == 4]
    assert resumed_losses[0] < first_loss, (resumed_losses[0], first_loss)


def test_world_from_hostfile(tmp_path):
    hf = tmp_path / "hosts"
    hf.write_text("# cluster\nhost1 slots=4\nhost2 slots=4\n\nhost3 slots=2 # tail\n")
    assert _world_from_hostfile(str(hf)) == 10


def test_agent_requires_one_membership_source(tmp_path):
    with pytest.raises(ValueError, match="exactly one"):
        ElasticAgent(["true"], {"elasticity": {}}, hostfile="a", world_file="b")
