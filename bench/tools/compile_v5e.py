#!/usr/bin/env python3
"""Compile a cell's programs at full size for a described v5e, with no chip,
and print each program's ``memory_analysis`` bytes.

    JAX_PLATFORMS=cpu python3 bench/tools/compile_v5e.py favorita.ridge

What a cell runs: the benchmark's data generator, then the covar batch
that ``ViewHandle.run`` dispatches.  The program's own functions are lowered
with shapes placed on the described chip, so what the chip's compiler would
refuse (a program that does not fit its 16 GB, a kernel it cannot build) is
refused here.
"""

from __future__ import annotations

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))


def main(name: str) -> None:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import repro
    from bench import run as bench_run
    from bench.lib import datagen
    from repro.core.schema import schema
    from repro.data.datasets import Dataset
    from repro.data.relations import Database, Relation
    from repro.ml.covar import covar_queries

    jax.config.update("jax_enable_compilation_cache", False)
    cell = bench_run.Cell(name)
    cfg = cell.cfg
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def dtype(a):
        return jnp.float32 if cfg.kinds[a] == "continuous" else jnp.int32

    def report(what, compiled):
        m = compiled.memory_analysis()
        print(f"{name}: {what}: arguments {m.argument_size_in_bytes} "
              f"outputs {m.output_size_in_bytes} temporaries "
              f"{m.temp_size_in_bytes} generated code "
              f"{m.generated_code_size_in_bytes} bytes", flush=True)

    # the generator
    gen = datagen.Generator(cfg)
    kdt = jax.random.key(0).dtype
    dkeys = {(r, a): sds((), kdt) for r in cfg.relations if r != cfg.fact
             for a in cfg.relation_attrs(r)}
    report("datagen dimensions",
           datagen.Generator._dims.lower(gen, dkeys).compile())
    dims = {r: {a: sds((cfg.n_rows(r),), dtype(a)) for a in cfg.attrs[r]}
            for r in cfg.relations if r != cfg.fact}
    fkeys = {a: sds((), kdt) for a in cfg.attrs[cfg.fact]}
    n = cfg.n_rows(cfg.fact)
    report("datagen fact", datagen.Generator._fact.lower(
        gen, fkeys, dims, n).compile())

    # the program, over shapes
    S = schema([tuple(a) for a in cfg.spec["attributes"]],
               [(r, cfg.attrs[r]) for r in cfg.relations])
    cols = {r: {a: sds((cfg.n_rows(r),), dtype(a)) for a in cfg.attrs[r]}
            for r in cfg.relations}
    data = Database(S, {r: Relation(r, c) for r, c in cols.items()})
    ds = Dataset(cfg.name, S, {}, [tuple(e) for e in cfg.edges],
                 cfg.features_cont, cfg.features_cat, cfg.label, cfg.fact,
                 _db=data)
    db = repro.connect(ds)
    qs, _ = covar_queries(ds)
    sizes = data.sizes()
    h = db.views(qs)
    print(f"{name}: {h.stats.summary()}")
    run = h.compiled.plan.bind(sizes)
    compiled = jax.jit(lambda c, p: run(c, p)).lower(cols, {}).compile()
    report("covar batch", compiled)
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    print(f"{name}: covar batch cost analysis: flops {cost.get('flops')} "
          f"bytes accessed {cost.get('bytes accessed')}", flush=True)


if __name__ == "__main__":
    for w in sys.argv[1:]:
        main(w)
