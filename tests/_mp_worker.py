"""Worker for the 2-process ``jax.distributed`` smoke test.

Launched twice by ``tests/test_multiprocess.py`` (process_id 0 and 1) on
the CPU backend with 4 virtual devices per process — the multi-host analog
of the reference's Flink mini-cluster tests (SURVEY.md §4): a coordinator
wires both processes into one runtime, a global 8-device mesh spans them,
``global_edge_block`` assembles globally-sharded columns from per-host
shards, and one sharded CC window step runs across the processes.

Prints ``MP_OK <labels...>`` on success (the parent asserts both workers
agree and exit 0).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
proc_id = int(sys.argv[1])
port = sys.argv[2]
# the launcher sets these in the subprocess env; set here too, before jax
# is imported, for standalone runs
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import jax  # noqa: E402
import numpy as np  # noqa: E402

from gelly_streaming_tpu.parallel import comm, multihost  # noqa: E402
from gelly_streaming_tpu.parallel.mesh import EDGE_AXIS, make_mesh  # noqa: E402
from gelly_streaming_tpu.summaries.labels import (  # noqa: E402
    cc_fold,
    init_labels,
    label_combine,
)

multihost.initialize(f"localhost:{port}", num_processes=2, process_id=proc_id)
assert jax.process_count() == 2, jax.process_count()
assert len(jax.devices()) == 8, len(jax.devices())
assert multihost.is_coordinator() == (proc_id == 0)

mesh = make_mesh(8)

# Each host owns a shard of the window's edges (the pre-partitioned ingest
# contract of parallel/multihost.py): host 0 links {0,1,2}, host 1 links
# {3,4} and bridges 2-3, so the global graph is one component {0..4} plus
# the untouched singleton 5 — correct ONLY if the cross-host edges meet in
# the collective.
V = 8
if proc_id == 0:
    src = np.array([0, 1, 0, 0], np.int32)
    dst = np.array([1, 2, 0, 0], np.int32)
    msk = np.array([True, True, False, False])
else:
    src = np.array([3, 2, 0, 0], np.int32)
    dst = np.array([4, 3, 0, 0], np.int32)
    msk = np.array([True, True, False, False])

gsrc, gdst, gmsk = multihost.global_edge_block(mesh, [src, dst, msk])
assert gsrc.shape == (8,), gsrc.shape

from jax.sharding import PartitionSpec as P  # noqa: E402


@jax.jit
def window_step(s, d, m):
    def shard_fn(s, d, m):
        part = cc_fold(init_labels(V), s, d, m)
        return jax.tree.map(lambda x: x[None], part)

    out = comm.shard_map(
        shard_fn, mesh,
        (P(EDGE_AXIS), P(EDGE_AXIS), P(EDGE_AXIS)),
        jax.tree.map(lambda _: P(EDGE_AXIS), init_labels(V)),
    )(s, d, m)
    # flat stacked-shard reduction (the engine's bulk combine)
    acc = jax.tree.map(lambda x: x[0], out)
    for i in range(1, 8):
        acc = label_combine(acc, jax.tree.map(lambda x: x[i], out))
    return acc


summary = window_step(gsrc, gdst, gmsk)
# global summaries are replicated; every process can read them
labels = np.asarray(jax.device_get(summary["labels"]))
touched = np.asarray(jax.device_get(summary["touched"]))
assert labels[:5].tolist() == [0, 0, 0, 0, 0], labels
assert touched.tolist() == [True] * 5 + [False] * 3, touched

# ---- the aggregation ENGINE itself across both processes: each host
# windows its own shard (dense ids -> identical mapping everywhere), the
# globalized stream feeds the engine's sharded window step ---------------
from gelly_streaming_tpu.core.stream import SimpleEdgeStream, StreamContext  # noqa: E402
from gelly_streaming_tpu.core.window import CountWindow  # noqa: E402
from gelly_streaming_tpu.datasets import IdentityDict  # noqa: E402
from gelly_streaming_tpu.library import ConnectedComponents  # noqa: E402

if proc_id == 0:
    esrc = np.array([0, 1, 6, 6], np.int64)
    edst = np.array([1, 2, 6, 6], np.int64)
else:
    esrc = np.array([3, 2, 6, 6], np.int64)
    edst = np.array([4, 3, 6, 6], np.int64)
# identical dense mapping on every host (no cross-host dict coordination)
from gelly_streaming_tpu.core.window import Windower  # noqa: E402

w = Windower(CountWindow(4), IdentityDict(8))
local = SimpleEdgeStream(
    _blocks=lambda: (b for _, b in w.blocks_from_chunks([(esrc, edst)])),
    _vdict=w.vertex_dict,
    context=StreamContext(mesh=mesh),
)
gstream = multihost.globalize_stream(local, mesh)
agg = ConnectedComponents(mesh=mesh)
last = None
for last in agg.run(gstream):
    pass
sets = sorted(last.component_sets())
assert sets == [frozenset({0, 1, 2, 3, 4}), frozenset({6})], sets

# ---- pre-partition ingest contract, STREAMING (round-4 verdict #8):
# a 64-edge random graph pre-partitioned across the two hosts, four
# windows per host, the engine's sharded window step per global window;
# the final components must equal a single-process union-find ----------


from _uf import union_find_components  # noqa: E402


def _uf_components(s, d):
    return union_find_components(zip(s.tolist(), d.tolist()))


rng = np.random.default_rng(77)  # identical global stream on both hosts
gsrc64 = rng.integers(0, 40, 64).astype(np.int64)
gdst64 = rng.integers(0, 40, 64).astype(np.int64)
# pre-partition: interleaved rows (the hash(edge) % n_hosts analog)
mine_s = gsrc64[proc_id::2]
mine_d = gdst64[proc_id::2]
w2 = Windower(CountWindow(8), IdentityDict(64))
local2 = SimpleEdgeStream(
    _blocks=lambda: (
        b for _, b in w2.blocks_from_chunks([(mine_s, mine_d)])
    ),
    _vdict=w2.vertex_dict,
    context=StreamContext(mesh=mesh),
)
g2 = multihost.globalize_stream(local2, mesh)
agg2 = ConnectedComponents(mesh=mesh)
n_windows = 0
final = None
for final in agg2.run(g2):
    n_windows += 1
assert n_windows == 4, n_windows
stream_sets = sorted(final.component_sets())
assert stream_sets == _uf_components(gsrc64, gdst64), stream_sets

# ---- dict-exchange ingest contract (a): sparse 40-bit raw ids, each
# host seeing a DIFFERENT shard; per-window allgather keeps the
# dictionaries byte-identical with no coordinator --------------------------
from gelly_streaming_tpu.core.vertexdict import VertexDict  # noqa: E402

pool = rng.integers(1 << 40, 1 << 41, size=48).astype(np.int64)
sp_src = pool[rng.integers(0, 48, 32)]
sp_dst = pool[rng.integers(0, 48, 32)]
my_src = sp_src[proc_id::2]
my_dst = sp_dst[proc_id::2]
vd = VertexDict()
enc = []
for k in range(4):  # four exchanged windows
    sl = slice(k * 4, (k + 1) * 4)
    sc, dc = multihost.dict_exchange_encode(
        mesh, vd, my_src[sl], my_dst[sl]
    )
    enc.append((sc, dc))
# the dictionary must be identical across hosts (the parent compares the
# printed line between processes) and must round-trip every id
assert len(vd) == len(np.unique(np.concatenate([sp_src, sp_dst]))), len(vd)
for (sc, dc), k in zip(enc, range(4)):
    sl = slice(k * 4, (k + 1) * 4)
    assert vd.decode(sc).tolist() == my_src[sl].tolist()
    assert vd.decode(dc).tolist() == my_dst[sl].tolist()
dict_sig = vd.raw_ids().tolist()

print(
    f"MP_OK {labels.tolist()} | {sorted(map(sorted, stream_sets))} | "
    f"{dict_sig}",
    flush=True,
)
