"""Elastic scaling: checkpoints move across mesh topologies.

A subprocess with 8 forced host devices saves a sharded train state on a
(data=2, model=4) mesh, then restores it onto a (data=4, model=2) mesh —
the failed-pod-exclusion / cluster-resize path — and verifies values and
continued training bit-compatibility of the loss computation.
"""

import json
import os
import subprocess
import sys

_PROG = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint import CheckpointManager
from repro.configs import get_config, reduced
from repro.launch.mesh import make_smoke_mesh
from repro.models import build_model
from repro.optim import adamw_init
from repro.sharding import rules

cfg = reduced(get_config("olmo-1b"))
model = build_model(cfg)

def named(tree, mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                        is_leaf=lambda x: isinstance(x, P))

mesh_a = make_smoke_mesh((2, 4), ("data", "model"))
with jax.set_mesh(mesh_a):
    params = model.init(jax.random.PRNGKey(0))
    opt = adamw_init(params)
    sh_a = named(rules.param_specs(cfg, params, mesh_a), mesh_a)
    params = jax.device_put(params, sh_a)

batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                                      cfg.vocab, jnp.int32)}
with jax.set_mesh(mesh_a):
    loss_a, _ = jax.jit(model.loss)(params, batch)

import shutil
shutil.rmtree("/tmp/elastic_ck", ignore_errors=True)
cm = CheckpointManager("/tmp/elastic_ck", keep_last=1)
cm.save(7, (params, opt), blocking=True)

# --- "cluster resized": new topology ---
mesh_b = make_smoke_mesh((4, 2), ("data", "model"))
like = jax.eval_shape(lambda: (model.init(jax.random.PRNGKey(0)),
                               adamw_init(model.init(jax.random.PRNGKey(0)))))
with jax.set_mesh(mesh_b):
    sh_b = (named(rules.param_specs(cfg, like[0], mesh_b), mesh_b),
            {"m": named(rules.param_specs(cfg, like[0], mesh_b), mesh_b),
             "v": named(rules.param_specs(cfg, like[0], mesh_b), mesh_b),
             "step": NamedSharding(mesh_b, P())})
    (params_b, opt_b), step, _ = cm.restore(like, shardings=sh_b)
    loss_b, _ = jax.jit(model.loss)(params_b, batch)

same = all(
    np.array_equal(np.asarray(x, np.float32), np.asarray(y, np.float32))
    for x, y in zip(jax.tree.leaves(params), jax.tree.leaves(params_b)))
print(json.dumps({"step": step, "same_values": bool(same),
                  "loss_a": float(loss_a), "loss_b": float(loss_b)}))
"""


def test_cross_mesh_restore():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    res = subprocess.run([sys.executable, "-c", _PROG],
                         capture_output=True, text=True, env=env,
                         cwd=os.path.dirname(os.path.dirname(__file__)),
                         timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["step"] == 7
    assert out["same_values"]
    assert abs(out["loss_a"] - out["loss_b"]) < 1e-2  # same math on new mesh
