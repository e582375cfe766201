"""The forest steps compiled for a DESCRIBED v5e, no chip attached: what
``memory_analysis()`` says of each, and whether the compiler keeps the
fixpoint's label table in fast memory.

    JAX_PLATFORMS=cpu python3 tools/step_memory.py [cc,cover,sized,v4]

Compiles ``jit_step`` at the benchmark cells' shapes (``cc``: cell 1's;
``cover``: the bip cell's; ``sized``: the sized CC cell's; ``v4``: the
four-chip cell's, one chip's share) with the TPU compiler installed
beside JAX (``on-chip-measurement`` guide, section 2: about a minute a
program, nothing runs, no time comes out of it) and prints one JSON line
a program: arguments, output, scratch and code in bytes, the
all-reduces of the compiled program, and ``fixpoint_carry``: the layout
of the carried label table of ``forest.fixpoint``'s ``while``. ``S(1)``
in it is the chip's fast memory. WHY IT IS WORTH A LOOK (PERF.md section
6, PR 37): where the compiler leaves that table in slow memory every
gather and scatter of every round costs twice, 8 ms of a 48 ms step, and
what decides it can be as far from the loop as which iota an earlier
sort was handed; the lowered text and every CPU test are the same either
way. It is a proxy read off the compiled text, not a measurement.
"""

from __future__ import annotations

import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TCAP, WCAP = 1 << 17, 1 << 16
_CARRY = re.compile(
    r'= (\S+) (?:get-tuple-element|while)\(.*'
    r'op_name="[^"]*forest\.fixpoint/while"')


def lowered(which: str, topo):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, SingleDeviceSharding
    from jax.sharding import PartitionSpec as P

    from gelly_streaming_tpu.parallel.mesh import VERTEX_AXIS, make_mesh
    from gelly_streaming_tpu.summaries import candidates, forest

    rep = rows = SingleDeviceSharding(topo.devices[0])
    mesh = None
    if which == "v4":
        mesh = make_mesh(n_edge_shards=1, n_vertex_shards=4,
                         devices=list(topo.devices))
        rep, rows = NamedSharding(mesh, P()), NamedSharding(
            mesh, P(VERTEX_AXIS))

    def lanes(n, dtype=jnp.int32):
        return jax.ShapeDtypeStruct((n,), dtype, sharding=rep)

    def table(n):
        return jax.ShapeDtypeStruct((n,), jnp.int32, sharding=rows)

    cols = (lanes(TCAP), lanes(TCAP, jnp.bool_), lanes(WCAP), lanes(WCAP))
    if which == "cover":
        latch = jax.ShapeDtypeStruct((), jnp.bool_, sharding=rep)
        return candidates._cover_step_fn(TCAP, WCAP, 1 << 27).lower(
            table(1 << 28), latch, *cols, lanes(WCAP, jnp.bool_))
    if which == "sized":
        return forest._forest_step_fn(TCAP, WCAP, 1 << 28, sizes=True).lower(
            table(1 << 28), *cols, table(1 << 28))
    vcap = 1 << (30 if which == "v4" else 28)
    return forest._forest_step_fn(TCAP, WCAP, vcap, mesh).lower(
        table(vcap), *cols)


def main() -> None:
    import jax
    from jax.experimental import topologies

    # a compile for a described chip can be written to the persistent
    # cache and never read back without one
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    which = sys.argv[1] if len(sys.argv) > 1 else "cc,cover,sized,v4"
    for name in which.split(","):
        compiled = lowered(name, topo).compile()
        text, m = compiled.as_text(), compiled.memory_analysis()
        carry = sorted({c for c in _CARRY.findall(text) if "[" in c
                        and not c.startswith("(")})
        print(json.dumps({
            "program": name, "arguments": m.argument_size_in_bytes,
            "output": m.output_size_in_bytes,
            "scratch": m.temp_size_in_bytes,
            "code": m.generated_code_size_in_bytes,
            "all_reduces": len(re.findall(
                r"= \S+ all-reduce(?:-start)?\(", text)),
            "fixpoint_carry": carry,
            "fixpoint_carry_in_fast_memory": any("S(1)" in c for c in carry),
        }), flush=True)


if __name__ == "__main__":
    main()
