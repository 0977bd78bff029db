"""Fixture: prove the tony.compile.* wiring reaches the user process and
the plan-instrumented step records cache hits/misses. Initializes the
runtime (which configures the persistent cache from the executor's
TONY_COMPILE_* env), compiles one tiny classifier step, and appends this
session's compile counters to $PROBE_OUT — one JSON line per run, so a
re-submitted job appends a second line the test compares."""
import json
import os
import sys

import tony_tpu.runtime as rt

ctx = rt.initialize()

import jax  # noqa: E402  (after initialize: cache config must precede use)
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# A cache placed from outside (JAX_COMPILATION_CACHE_DIR) wins over the
# conf the executor exported; otherwise the conf's dir must have arrived.
expected = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
    os.environ.get("TONY_COMPILE_CACHE_DIR", "")
if expected != jax.config.jax_compilation_cache_dir:
    print("compile cache env not wired into jax config", file=sys.stderr)
    sys.exit(2)

from tony_tpu.models import MnistConfig  # noqa: E402
from tony_tpu.models.train import make_classifier_step  # noqa: E402
from tony_tpu.parallel.mesh import MeshSpec, build_mesh  # noqa: E402

mesh = build_mesh(MeshSpec(), devices=jax.devices()[:1])
init_fn, step_fn = make_classifier_step(
    MnistConfig(arch="mlp", dtype="float32"), mesh
)
rng = np.random.default_rng(0)
images = jnp.asarray(rng.normal(size=(8, 28, 28, 1)), jnp.float32)
labels = jnp.asarray(rng.integers(0, 10, (8,)), jnp.int32)
state = init_fn(jax.random.key(0))
state, metrics = step_fn(state, images, labels)
assert np.isfinite(float(metrics["loss"]))

from tony_tpu import observability  # noqa: E402

counters = observability.default_registry().snapshot()["counters"]
with open(os.environ["PROBE_OUT"], "a") as f:
    f.write(json.dumps(counters) + "\n")
