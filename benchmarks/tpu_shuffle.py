"""TPU-adaptation benchmark: flat vs blob-hierarchical MoE dispatch.

Spawns a subprocess with 8 forced CPU devices (2 pods × 2 data × 2
model) and reports, per mode: wall time per step on those CPU devices,
inter-pod (DCN) payload bytes, and HLO collective statistics from the
compiled module. The child runs on the CPU whatever the parent's
platform: a parent that holds a chip would leave the child none.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from typing import List, Tuple

Row = Tuple[str, float, str]

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_BODY = """
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import json, time
import jax, jax.numpy as jnp
from repro.launch.mesh import make_test_mesh
from repro.launch import hlo_analysis as H
from repro.shuffle.api import ShuffleConfig, ep_moe_ffn

mesh = make_test_mesh(devices=8)
E, k, d, de, T = 16, 2, 64, 128, 4096
ks = jax.random.split(jax.random.key(0), 5)
x = jax.random.normal(ks[0], (T, d), jnp.bfloat16)
wr = jax.random.normal(ks[1], (d, E), jnp.float32) * 0.3
wg = jax.random.normal(ks[2], (E, d, de), jnp.bfloat16)
wu = jax.random.normal(ks[3], (E, d, de), jnp.bfloat16)
wd = jax.random.normal(ks[4], (E, de, d), jnp.bfloat16)
out = {}
for mode, compress in (('direct', False), ('blob', False), ('blob', True)):
    cfg = ShuffleConfig(mode=mode, token_axes=('pod','data','model'),
                        expert_axes=('pod','model'), capacity_factor=1.25,
                        compress_dcn=compress)
    f = jax.jit(lambda x: ep_moe_ffn(x, wr, wg, wu, wd, top_k=k, cfg=cfg,
                                     mesh=mesh)[0::2])
    comp = f.lower(x).compile()
    st = H.analyze(comp.as_text(), num_devices=8, devices_per_pod=4)
    y, diag = f(x)
    jax.block_until_ready(y)
    t0 = time.perf_counter()
    for _ in range(5):
        y, diag = f(x)
    jax.block_until_ready(y)
    us = (time.perf_counter() - t0) / 5 * 1e6
    key = mode + ('+int8' if compress else '')
    out[key] = {'us': us, 'dcn_bytes': float(diag.dcn_bytes),
                'dropped': int(diag.dropped),
                'hlo_collective_bytes': st.collective_bytes,
                'hlo_dcn_bytes': st.dcn_collective_bytes,
                'hlo_collective_count': st.collective_count}
print('RESULT ' + json.dumps(out))
"""


def run() -> List[Row]:
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(_BODY)],
                       env=env, capture_output=True, text=True, timeout=900)
    if r.returncode != 0:
        raise RuntimeError(f"tpu_shuffle child failed (rc {r.returncode}):"
                           f"\n{r.stderr}")
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT ")][0]
    data = json.loads(line[len("RESULT "):])
    rows: List[Row] = []
    base = data["direct"]
    for mode, d in data.items():
        rows.append((
            f"tpu_shuffle.{mode}", d["us"],
            f"dcn={d['dcn_bytes'] / 1e6:.2f}MB "
            f"hlo_coll={d['hlo_collective_bytes'] / 1e6:.2f}MB "
            f"hlo_dcn={d['hlo_dcn_bytes'] / 1e6:.2f}MB "
            f"n_coll={d['hlo_collective_count']} "
            f"dcn_vs_direct={d['dcn_bytes'] / max(base['dcn_bytes'], 1):.2f}x"
        ))
    return rows
