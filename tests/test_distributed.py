"""Distribution correctness on an 8-device host mesh (subprocess so the
XLA device-count flag never leaks into other tests)."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

#: the checkout under test, so the subprocess imports this tree's ``src``
ROOT = Path(__file__).resolve().parents[1]

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
import numpy as np
from jax import make_mesh
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

mesh = make_mesh((4, 2), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
results = {}

# 1) the AND serve step under an 8-device mesh
from repro.core.anchors import build_anchored
from repro.serving.engine import make_serve_step
from repro.serving.plan import AND
lists = []
r2 = np.random.default_rng(7)
for w in range(20):
    present = np.repeat(r2.random(40) < 0.4, 10) ^ (r2.random(400) < 0.02)
    l = np.flatnonzero(present).astype(np.int64)
    lists.append(l if len(l) else np.asarray([1], dtype=np.int64))
aidx = build_anchored(lists)
serve = jax.jit(make_serve_step(max_terms=3, mode=AND))
index_arrays = {"anchors": aidx.anchors, "c_offsets": aidx.c_offsets,
                "expand": aidx.expand, "expand_valid": aidx.expand_valid,
                "lengths": aidx.lengths}
qt = jnp.asarray([[0, 3, 0], [5, 9, 2]], jnp.int32)
ql = jnp.asarray([2, 3], jnp.int32)
with mesh:
    vals, mask = serve(index_arrays, qt, ql)
ref = np.intersect1d(lists[0], lists[3])
got = np.unique(np.asarray(vals[0])[np.asarray(mask[0])])
cand_cap = np.asarray(vals[0]).max()
results["uihrdc_ok"] = bool(np.array_equal(got, ref[ref <= cand_cap]))

# 2) document-partitioned serving via shard_map (4 shards on the data axis)
from repro.serving.partitioned import PartitionedAnchoredIndex, make_partitioned_serve_step, merge_results
pidx = PartitionedAnchoredIndex.build(lists, n_docs=400, n_shards=4)
serve_p = make_partitioned_serve_step(max_terms=2, mesh=mesh, shard_axis="data")
qt2 = jnp.asarray([[0, 3], [5, 9]], jnp.int32)
ql2 = jnp.asarray([2, 2], jnp.int32)
with mesh:
    arrays_sh = {k: jax.device_put(v, NamedSharding(mesh, P("data", *([None] * (v.ndim - 1)))))
                 for k, v in pidx.arrays.items()}
    pv, pm = serve_p(arrays_sh, qt2, ql2)
merged = merge_results(np.asarray(pv), np.asarray(pm))
ref2 = np.intersect1d(lists[0], lists[3])
results["partitioned_ok"] = bool(np.isin(merged[0], ref2).all() and len(merged[0]) > 0)

print(json.dumps(results))
"""


@pytest.fixture(scope="module")
def dist_results():
    import jax

    # the subprocess emulates an 8-device mesh via the host-platform flag,
    # which only works on CPU backends; on a real accelerator host we need
    # 8 physical devices.  Skip cleanly anywhere else (single-GPU boxes).
    if jax.default_backend() != "cpu" and jax.device_count() < 8:
        pytest.skip("needs 8 devices (or CPU host-platform emulation)")
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": os.environ.get("HOME", str(ROOT))},
        timeout=540, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_uihrdc_distributed(dist_results):
    assert dist_results["uihrdc_ok"]


def test_partitioned_shard_map(dist_results):
    assert dist_results["partitioned_ok"]
