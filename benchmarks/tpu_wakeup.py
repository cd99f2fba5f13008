"""Beyond-paper: TPU-native multipath model wake-up.

The paper's relay insight generalized to a pod (DESIGN.md §2.1): weights
enter host-chunked over every chip's PCIe path (multipath ingest) and an
ICI collective schedule assembles the serving layout. This benchmark
reports, for a reduced arch on an 8-virtual-chip host:

  * the compiled ICI assembly bytes (from HLO, via a subprocess so the
    device count doesn't leak), and
  * the simulated PCIe ingest time: N-path chunked landing vs single-path
    native (the MMA engine on the tpu_host topology).
"""
import os
import subprocess
import sys

from repro.core import Direction, MMAConfig, SimWorld
from repro.core.engine import MMAEngine
from repro.core.task_launcher import SimBackend
from repro.core.topology import tpu_host

from .common import CSV

_SUB = r"""
import jax
from repro.configs import get_config
from repro.distributed import make_wakeup_step
from repro.launch.mesh import make_host_mesh
from repro.launch.roofline import collective_stats
from repro.models.init import abstract_params, param_bytes
cfg = get_config("tinyllama-1.1b").reduced()
mesh = make_host_mesh(model=4)   # 2 data x 4 model, Auto axes
fn, _, _ = make_wakeup_step(cfg, mesh)
with jax.set_mesh(mesh):
    compiled = fn.lower(abstract_params(cfg)).compile()
cs = collective_stats(compiled.as_text())
print("BYTES", param_bytes(cfg), cs.total_bytes,
      sum(cs.count_by_kind.values()))
"""


def run(csv: CSV) -> None:
    print("# TPU-native multipath wake-up (beyond-paper)")
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    # The child only counts HLO on virtual CPU devices; pinning it to the
    # CPU keeps it off a TPU that this process may already hold.
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = "src"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run([sys.executable, "-c", _SUB], env=env,
                         capture_output=True, text=True, cwd=root,
                         timeout=600)
    if out.returncode != 0:
        raise RuntimeError(out.stderr[-800:])
    line = [l for l in out.stdout.splitlines() if l.startswith("BYTES")][0]
    _, pbytes, coll_bytes, n_coll = line.split()
    print(f"weights {int(pbytes) / (1 << 20):.1f} MB -> ICI assembly "
          f"{int(coll_bytes) / (1 << 20):.1f} MB/chip over {n_coll} "
          f"collectives (8 virtual chips, 2x4 mesh)")
    csv.add("tpu_wakeup.ici_mb_per_chip", 0.0,
            f"{int(coll_bytes) / (1 << 20):.1f}")

    # PCIe ingest: 4-path chunked landing vs single-path, v5e host topology
    topo = tpu_host(n_chips=4)
    weights = 2 * 10 * (1 << 30)   # a 10B-param bf16 wake-up payload
    world = SimWorld()
    cfg = MMAConfig()
    backend = SimBackend(world, topo, cfg)
    eng = MMAEngine(topo, backend, cfg)
    t = eng.memcpy(weights, device=0, direction=Direction.H2D)
    world.run()
    multi = t.elapsed
    world2 = SimWorld()
    backend2 = SimBackend(world2, topo, cfg)
    res = {}
    backend2.native_copy(weights, 0, Direction.H2D,
                         lambda: res.setdefault("t", world2.now))
    world2.run()
    single = res["t"]
    print(f"10B-param bf16 ingest on a 4-chip v5e host: single-path "
          f"{single:.2f}s -> multipath {multi:.2f}s "
          f"({single / multi:.2f}x)")
    csv.add("tpu_wakeup.ingest_speedup", multi * 1e6,
            f"{single / multi:.2f}x")


if __name__ == "__main__":
    c = CSV()
    run(c)
    c.emit()
