#!/usr/bin/env python3
"""Write a cell's covar batch as the optimized HLO a described v5e compiles,
with no chip, with its metadata stripped, so that two commits' programs can
be compared op for op.

    JAX_PLATFORMS=cpu python3 bench/tools/hlo_v5e.py favorita.ridge out.hlo
    diff parent.hlo out.hlo

Stripped: each instruction's ``metadata={...}`` (op names and source
lines, which scopes and edits elsewhere change) and the module's table of
source files and stack frames.  What is left is every op, shape, layout
and fusion.  Built as ``bench/tools/compile_v5e.py`` builds it: the
program's functions over shapes placed on the described chip.
"""

from __future__ import annotations

import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

_METADATA = re.compile(r", metadata=\{[^{}]*\}")
_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def strip(text: str) -> str:
    """HLO text without metadata or the source tables."""
    out, skip = [], False
    for line in text.splitlines():
        if line.strip() in _TABLES:
            skip = True
        elif skip and not line.strip():
            skip = False
            continue
        if not skip:
            out.append(_METADATA.sub("", line))
    return "\n".join(out) + "\n"


def batch_text(name: str) -> str:
    """The optimized HLO of a cell's covar batch, compiled for one chip of
    a described v5e."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import repro
    from bench import run as bench_run
    from repro.core.schema import schema
    from repro.data.datasets import Dataset
    from repro.data.relations import Database, Relation
    from repro.ml.covar import covar_queries

    jax.config.update("jax_enable_compilation_cache", False)
    cfg = bench_run.Cell(name).cfg
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def sds(r, a):
        dt = jnp.float32 if cfg.kinds[a] == "continuous" else jnp.int32
        return jax.ShapeDtypeStruct((cfg.n_rows(r),), dt, sharding=chip)

    S = schema([tuple(a) for a in cfg.spec["attributes"]],
               [(r, cfg.attrs[r]) for r in cfg.relations])
    cols = {r: {a: sds(r, a) for a in cfg.attrs[r]} for r in cfg.relations}
    data = Database(S, {r: Relation(r, c) for r, c in cols.items()})
    ds = Dataset(cfg.name, S, {}, [tuple(e) for e in cfg.edges],
                 cfg.features_cont, cfg.features_cat, cfg.label, cfg.fact,
                 _db=data)
    qs, _ = covar_queries(ds)
    run = repro.connect(ds).views(qs).compiled.plan.bind(data.sizes())
    return jax.jit(lambda c, p: run(c, p)).lower(cols, {}).compile().as_text()


if __name__ == "__main__":
    with open(sys.argv[2], "w") as f:
        f.write(strip(batch_text(sys.argv[1])))
