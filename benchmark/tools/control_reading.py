"""Read a serving cell's controls on the chip: one run of the cell with
``control=True``, which a benchmark run never asks for. Prints the sound
run's checks and, for every control the configuration's ``check.py`` plays
(the reference with one departure, put in the program's place on the same
prompts and beams), its numbers beside the limits: the readings the limits
in ``config.json`` are set between. Run by hand:

    python3 -m benchmark.tools.control_reading <workload> <seed> [seconds]
"""

from __future__ import annotations

import json
import os
import sys
import time

_T_BEGIN = time.monotonic()


def main(argv) -> int:
    workload, seed = argv[0], int(argv[1])
    from benchmark.harness import device as devmod
    from benchmark.harness.spec import Spec

    spec = Spec()
    cell = spec.cell(workload)
    seconds = float(argv[2]) if len(argv) > 2 else float(spec.doc["run_seconds"])
    devmod.enable_compile_cache()
    devmod.require_chips(cell.chips)
    result = cell.kind.run(cell, seed, seconds, False, _T_BEGIN, control=True)
    print(json.dumps({
        "workload": workload, "seed": seed, "attempted": result["attempted"],
        "failed": result["failed"], "checks": result["checks"],
        "e2e": result["e2e"],
        "checked_requests": result.get("checked_requests"),
        "controls": result.get("controls"),
        "control_checks": result.get("control_checks"),
    }), flush=True)
    return 0


if __name__ == "__main__":
    code = main(sys.argv[1:])
    sys.stdout.flush()
    os._exit(code)  # daemon threads of the program must not hold the exit
