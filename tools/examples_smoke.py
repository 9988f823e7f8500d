"""CI smoke for the engine examples on the session API.

    PYTHONPATH=src EXAMPLES_SCALE=0.02 python tools/examples_smoke.py

Runs every ``examples/*.py`` aggregate-engine example in-process at small
scale and FAILS if any :class:`repro.core.engine.EngineDeprecationWarning`
fires — i.e. if an example, or anything inside the ``repro`` package it
calls, still routes through the deprecated ``Engine.compile`` /
``Engine.compile_incremental`` entry points instead of the facade.  (The
dedicated warning category keeps the gate sharp: third-party
DeprecationWarnings cannot trip it.)
"""

import os
import runpy
import sys
import time
import traceback
import warnings

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ENGINE_EXAMPLES = [
    "quickstart.py",
    "ridge_over_joins.py",
    "decision_tree.py",
    "chow_liu_cubes.py",
    "streaming_ridge.py",
]


def main() -> int:
    os.environ.setdefault("EXAMPLES_SCALE", "0.02")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, os.path.join(REPO, "src"))
    from repro.core.engine import EngineDeprecationWarning

    warnings.simplefilter("error", EngineDeprecationWarning)
    failed = []
    for name in ENGINE_EXAMPLES:
        path = os.path.join(REPO, "examples", name)
        t0 = time.time()
        print(f"=== {name} (EXAMPLES_SCALE={os.environ['EXAMPLES_SCALE']})",
              flush=True)
        try:
            runpy.run_path(path, run_name="__main__")
            print(f"=== {name} OK [{time.time() - t0:.1f}s]", flush=True)
        except EngineDeprecationWarning:
            traceback.print_exc()
            print(f"=== {name} FAILED: deprecated Engine entry point used "
                  "(port it to repro.connect / Database.views)", flush=True)
            failed.append(name)
        except Exception:
            traceback.print_exc()
            print(f"=== {name} FAILED", flush=True)
            failed.append(name)
    if failed:
        print(f"examples smoke: {len(failed)} failed: {', '.join(failed)}")
        return 1
    print(f"examples smoke: all {len(ENGINE_EXAMPLES)} passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
