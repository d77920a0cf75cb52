"""Multi-host (DCN) runtime: a real 2-process jax.distributed job on CPU.

Spawns two coordinator-joined worker processes (Gloo CPU collectives, 2
virtual devices each → a 4-device global mesh), runs the full sequence-
parallel train step with dp *crossing the process boundary* — the
gradient all-reduce rides the inter-process link exactly as it would ride
DCN between TPU slices — and a dp-only Trainer step fed through the
process-local batch path.  Both processes must agree bit-exactly on the
resulting losses."""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_WORKER = r"""
import json, sys
import jax

jax.config.update("jax_platforms", "cpu")
pid, port = int(sys.argv[1]), sys.argv[2]
from fmda_tpu.parallel import distributed

distributed.initialize(f"localhost:{port}", num_processes=2, process_id=pid)
assert jax.process_count() == 2, jax.process_count()
assert jax.device_count() == 4, jax.device_count()

import jax.numpy as jnp
import numpy as np
import optax

from fmda_tpu.config import MeshConfig, ModelConfig, TrainConfig
from fmda_tpu.models.bigru import BiGRU
from fmda_tpu.parallel import build_mesh
from fmda_tpu.parallel.distributed import shard_train_inputs_multihost
from fmda_tpu.parallel.sp_train import make_sp_train_step

# ---- sp train step over the global mesh: dp=2 across hosts, sp=2 local
mesh = build_mesh(MeshConfig(dp=2, sp=2, processes=2))
cfg = ModelConfig(hidden_size=8, n_features=12, output_size=4, dropout=0.0,
                  use_pallas=False)
batch, seq = 4, 8  # global batch 4 -> 2 rows per host
model = BiGRU(cfg)
r = np.random.default_rng(0)
x_global = r.normal(size=(batch, seq, cfg.n_features)).astype(np.float32)
y_global = (x_global[:, -1, :4] > 0).astype(np.float32)
lo, hi = pid * 2, pid * 2 + 2  # this host's rows
variables = model.init({"params": jax.random.PRNGKey(0)},
                       jnp.asarray(x_global[:1]))
optimizer = optax.chain(optax.clip_by_global_norm(50.0), optax.adam(1e-3))
opt_state = optimizer.init(variables["params"])
step = make_sp_train_step(mesh, cfg, seq, optimizer,
                          weight=jnp.ones(4), pos_weight=jnp.ones(4))
x, y, params, opt_state = shard_train_inputs_multihost(
    mesh, x_global[lo:hi], y_global[lo:hi], variables["params"], opt_state)
params, opt_state, loss = step(params, opt_state, x, y)
sp_loss = float(jax.device_get(loss))

# ---- ring-attention sp train step over the SAME global mesh: the K/V
# ring rides the local sp axis while the gradient all-reduce crosses the
# process boundary (DCN dp) exactly as the recurrent program's does
from fmda_tpu.models import build_model

attn_cfg = ModelConfig(hidden_size=8, n_features=12, output_size=4,
                       dropout=0.0, spatial_dropout=False, cell="attn",
                       n_heads=2)
attn_params = build_model(attn_cfg).init(
    {"params": jax.random.PRNGKey(1)}, jnp.asarray(x_global[:1]))["params"]
attn_opt = optimizer.init(attn_params)
attn_step = make_sp_train_step(mesh, attn_cfg, seq, optimizer,
                               weight=jnp.ones(4), pos_weight=jnp.ones(4))
xa, ya, attn_params, attn_opt = shard_train_inputs_multihost(
    mesh, x_global[lo:hi], y_global[lo:hi], attn_params, attn_opt)
_, _, attn_loss = attn_step(attn_params, attn_opt, xa, ya)
attn_loss = float(jax.device_get(attn_loss))

# ---- dp-only Trainer step through the process-local batch path
from fmda_tpu.data.pipeline import Batch
from fmda_tpu.train import Trainer

dp_mesh = build_mesh(MeshConfig(dp=4, sp=1, processes=2))
trainer = Trainer(cfg, TrainConfig(batch_size=batch, window=seq),
                  weight=np.ones(4, np.float32),
                  pos_weight=np.ones(4, np.float32), mesh=dp_mesh)
state = trainer.init_state(jax.random.PRNGKey(0))
local = Batch(x=x_global[lo:hi], y=y_global[lo:hi],
              mask=np.ones(2, np.float32))
placed = next(iter(trainer._place_batches([local])))
state, vals = trainer.single_step(state, placed, jax.random.PRNGKey(1))
tr_loss = float(jax.device_get(vals.loss))

print(json.dumps({"pid": pid, "sp_loss": sp_loss, "trainer_loss": tr_loss,
                  "attn_loss": attn_loss}))
"""


def test_two_process_dp_across_hosts(tmp_path):
    with socket.socket() as s:  # free port for the coordinator
        s.bind(("localhost", 0))
        port = s.getsockname()[1]

    worker_py = tmp_path / "worker.py"
    worker_py.write_text(_WORKER)
    from fmda_tpu.utils.env import cpu_forced_env

    env = cpu_forced_env(2, repo_dir=REPO)

    procs = [
        subprocess.Popen(
            [sys.executable, str(worker_py), str(i), str(port)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        )
        for i in range(2)
    ]
    results = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=240)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("distributed worker timed out")
        assert p.returncode == 0, err.decode(errors="replace")[-1500:]
        results.append(json.loads(out.decode().strip().splitlines()[-1]))

    (a, b) = results
    assert np.isfinite(a["sp_loss"])
    # the all-reduced loss must be identical on both hosts — this is the
    # cross-process gradient/loss agreement DCN dp guarantees
    assert a["sp_loss"] == b["sp_loss"]
    assert a["trainer_loss"] == b["trainer_loss"]
    assert np.isfinite(a["trainer_loss"])
    # the ring-attention program must agree across hosts the same way
    assert a["attn_loss"] == b["attn_loss"]
    assert np.isfinite(a["attn_loss"])
