"""Quickstart: the NoMora scheduler + a tiny LM, end to end in ~a minute.

1. Build a small simulated data center with a live latency plane.
2. Schedule a mixed workload with the NoMora policy and compare against
   the random baseline (the paper's headline experiment, Fig. 5).
3. Train a tiny qwen3-family model for a few steps with the production
   train step (FSDP+TP sharding rules, remat, AdamW).

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import latency, simulator, topology, workload
from repro.core.policy import PolicyParams
from repro.data import DataConfig, SyntheticLMData
from repro.launch.mesh import make_mesh
from repro.launch.train import reduce_config
from repro import configs
from repro.runtime import enable_compilation_cache
from repro.models import LM
from repro.optim import AdamW, AdamWConfig
from repro.train import steps as train_steps


def schedule_demo():
    print("=== NoMora scheduling (paper Fig. 5, miniature) ===")
    topo = topology.Topology(
        n_machines=128, machines_per_rack=16, racks_per_pod=4, slots_per_machine=4
    )
    plane = latency.LatencyPlane.synthesize(topo, duration_s=240, seed=0)
    wl = workload.synth_workload(topo, duration_s=240, seed=1, target_utilisation=0.7)
    for pol in ("random", "nomora"):
        cfg = simulator.SimConfig(
            policy=pol, params=PolicyParams(p_m=105, p_r=110), seed=2
        )
        m = simulator.simulate(wl, plane, cfg)
        s = m.summary()
        print(
            f"  {pol:8s}: avg app-performance area {s['avg_app_perf_area']:.1f}% "
            f"({int(s['tasks_placed'])} tasks placed)"
        )


def train_demo():
    print("=== Tiny LM training (production train step) ===")
    cfg = reduce_config(configs.get_config("qwen3-0.6b"), factor=16)
    lm = LM(cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    opt = AdamW(AdamWConfig(lr=3e-3))
    step, state_sh, _ = train_steps.build_train_step(lm, opt, mesh, remat=True)
    params = lm.init(jax.random.PRNGKey(0), dtype=jnp.float32)
    state = jax.device_put(opt.init(params), state_sh)
    data = SyntheticLMData(
        DataConfig(vocab_size=cfg.vocab_size, seq_len=128, global_batch=4)
    )
    losses = []
    for i in range(20):
        batch = {k: jnp.asarray(v) for k, v in data.batch(i).items()}
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    print(f"  loss: step0 {losses[0]:.3f} -> step19 {losses[-1]:.3f} "
          f"({'decreasing OK' if losses[-1] < losses[0] else 'NOT decreasing'})")


if __name__ == "__main__":
    enable_compilation_cache()
    schedule_demo()
    train_demo()
