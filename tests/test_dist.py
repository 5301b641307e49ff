"""Logical-axis sharding rules + a cross-pod train step on fake devices."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.dist import logical_to_spec, use_rules
from repro.launch.mesh import make_local_mesh

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_rules_divisibility_fallback():
    mesh = make_local_mesh(data=1, model=1)
    with use_rules(mesh):
        # dim 24 on a 1-wide axis always divides; use a fake 16 via rules math
        spec = logical_to_spec(("batch", "heads"), (8, 24))
        assert isinstance(spec, P)


def test_rules_dedup_first_binding_wins():
    mesh = make_local_mesh(data=1, model=1)
    with use_rules(mesh, {"expert": "model", "expert_cap": "model", "ff": "model"}):
        spec = logical_to_spec(("expert", "expert_cap", "ff"), (4, 4, 4))
        # only the first gets 'model'; later duplicates are dropped
        assert spec[0] == "model"
        assert spec[1] is None and spec[2] is None


def test_rules_missing_axis_filtered():
    mesh = make_local_mesh(data=1, model=1)  # no 'pod' axis
    with use_rules(mesh):
        spec = logical_to_spec(("batch",), (8,))
        # ('pod','data') filtered to ('data',)
        assert spec[0] == ("data",) or spec[0] == "data"


def test_constrain_noop_outside_mesh():
    from repro.dist import constrain

    x = jnp.ones((4, 4))
    y = constrain(x, ("batch", None))
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_crosspod_trainstep_on_fake_devices():
    """shard_map cross-pod step (int8 compression) compiles on a (2,2,2) mesh."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from repro.configs import get_smoke_config
from repro.dist import use_rules
from repro.models import init_params
from repro.train import OptConfig, init_opt, init_error_feedback, make_train_step_crosspod
mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
cfg = get_smoke_config("yi_34b")
with use_rules(mesh):
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = init_opt(params)
    err = init_error_feedback(params)
    step = make_train_step_crosspod(cfg, OptConfig(), mesh, compress=True)
    batch = {"tokens": jnp.ones((8, 16), jnp.int32)}
    p2, o2, e2, m = jax.jit(step)(params, opt, err, batch)
    assert jnp.isfinite(m["loss"]).all()
print("CROSSPOD_OK", float(m["loss"]))
"""
    env = dict(os.environ, PYTHONPATH=SRC)
    r = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True, text=True)
    assert r.returncode == 0, (r.stdout[-1000:], r.stderr[-3000:])
    assert "CROSSPOD_OK" in r.stdout
