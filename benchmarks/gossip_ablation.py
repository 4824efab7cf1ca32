"""Beyond-paper ablation: gossip (DMF protocol) vs centralized all-reduce on
a small LM — loss parity and consensus, quantified (EXPERIMENTS.md §Perf-B
semantics note). A loss-only ablation: it runs in a child process pinned
to the CPU backend (``JAX_PLATFORMS=cpu``) with 8 host devices, so it never
competes with the harness for an accelerator — a chip belongs to one
process, and the harness has already touched JAX. A failed child raises.

Writes ``BENCH_gossip_ablation.json`` (repo root + benchmarks/results
mirror, the `common.save_json` BENCH_* convention). The subprocess hands
its result back through a temp FILE, not stdout — the snippet previously
ended in a stray module-scope json print, making the whole bench depend
on stdout's last line staying clean (any library chatter broke the
parse)."""
from __future__ import annotations

import json
import pathlib
import subprocess
import sys
import tempfile

from benchmarks import common

REPO = pathlib.Path(__file__).resolve().parents[1]

CODE = """
import json, sys
import jax, jax.numpy as jnp
from repro.configs import registry
from repro.core import gossip as gossip_lib
from repro.data.lm_pipeline import LMDataConfig, SyntheticLM
from repro.launch.train import make_train_step
from repro.models import config as mc
from repro.optim import adamw

mesh = jax.make_mesh((4, 2), ("data", "model"))
cfg = mc.reduced(registry.get_config("qwen1.5-4b"), n_kv_heads=2, vocab_size=256,
                 d_model=128, d_ff=256, n_heads=4, head_dim=32)
data = SyntheticLM(LMDataConfig(vocab_size=256, seq_len=64, batch_size=16, seed=0))
out = {}
for name, sync, D in [("allreduce", "allreduce", 0), ("gossip_d1", "gossip", 1),
                      ("gossip_d2", "gossip", 2)]:
    g = gossip_lib.GossipConfig(learner_axis="data", walk_length=max(D, 1))
    step, init_fn, _ = make_train_step(cfg, mesh, adamw(6e-3), sync=sync, gossip=g)
    state = init_fn(jax.random.PRNGKey(0))
    losses = []
    cons = None
    for i in range(50):
        b = {k: jnp.asarray(v) for k, v in data.batch(i).items()}
        state, m = step(state, b)
        losses.append(round(float(m["loss"]), 4))
        if "consensus_err" in m:
            cons = round(float(m["consensus_err"]), 4)
    out[name] = {"first": losses[0], "last": losses[-1],
                 "curve10": losses[::5], "consensus_err": cons}
with open(sys.argv[1], "w") as f:
    json.dump(out, f)
"""


def main(steps: int = 50):
    import os
    env = {**os.environ,
           "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "PYTHONPATH": str(REPO / "src")}
    with tempfile.NamedTemporaryFile(suffix=".json", delete=False) as tmp:
        out_path = pathlib.Path(tmp.name)
    try:
        res = subprocess.run(
            [sys.executable, "-c", CODE, str(out_path)], capture_output=True,
            text=True, timeout=2400, env=env)
        if res.returncode != 0:
            raise RuntimeError(
                f"gossip ablation child exited {res.returncode}:\n"
                f"{res.stderr[-1500:]}")
        data = json.loads(out_path.read_text())
    finally:
        out_path.unlink(missing_ok=True)
    gap = data["gossip_d1"]["last"] - data["allreduce"]["last"]
    data["gossip_minus_allreduce_final_loss"] = round(gap, 4)
    common.save_json("BENCH_gossip_ablation", data)  # mirrors to repo root
    return data


if __name__ == "__main__":
    print(json.dumps(main(), indent=1))
