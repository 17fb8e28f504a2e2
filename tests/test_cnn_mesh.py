"""The paper's own DSCNNs on the production mesh: batch-parallel integer
inference lowers + compiles across 256 chips (subprocess: needs 512 fake
devices without leaking XLA_FLAGS into the main test process)."""
import os
import subprocess
import sys

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str) -> str:
    env = dict(os.environ, PYTHONPATH=SRC)
    prelude = ("import os\n"
               "os.environ['XLA_FLAGS']="
               "'--xla_force_host_platform_device_count=512'\n")
    out = subprocess.run([sys.executable, "-c", prelude + code],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_mobilenet_qnet_inference_compiles_on_mesh():
    out = _run("""
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import cu, qnet as Q
from repro.core.calibrate import calibrate
from repro.core.quant import QuantConfig
from repro.launch.mesh import make_production_mesh
from repro.models import layers, mobilenet_v2 as mnv2

# build + quantize a small-but-real MobileNet-V2 design point
net = mnv2.build(alpha=0.35, input_hw=96, num_classes=1000)
params = layers.init_params(jax.random.PRNGKey(0), net)
def apply_fn(p, b):
    return layers.forward(p, b, net, capture=True)[1]
cal = [jax.random.uniform(jax.random.PRNGKey(i), (1, 96, 96, 3),
                          minval=-1, maxval=1) for i in range(2)]
obs = calibrate(apply_fn, params, cal, QuantConfig(4, False, None))
qn = Q.quantize_net(params, net, obs)

# batch-parallel integer inference across the single-pod mesh
mesh = make_production_mesh()
x_spec = jax.ShapeDtypeStruct((1024, 96, 96, 3), jnp.float32)
in_sh = NamedSharding(mesh, P(("data",), None, None, None))
out_sh = NamedSharding(mesh, P(("data",), None))
fn = jax.jit(lambda x: cu.run_qnet(qn, x), in_shardings=in_sh,
             out_shardings=out_sh)
compiled = fn.lower(x_spec).compile()
mem = compiled.memory_analysis()
ca = compiled.cost_analysis()
assert mem.temp_size_in_bytes < 2e9  # tiny per-chip working set
print("OK flops/dev=%.2e temp=%.1fMB" % (
    float(ca.get("flops", 0)), mem.temp_size_in_bytes / 1e6))
""")
    assert "OK" in out
