"""Run the chaos catalog on both cluster transports.

    python -m goworld_tpu.chaos

For each transport (tcp, then uds) this runs the single-cluster scenario
suite (``run_chaos``) and the migrate-during-dispatcher-restart phase on
the two-game cluster (``run_multigame``), judges the ``[slo]`` budgets of
the loaded config, and prints one JSON summary. Exit code 1 if any
scenario failed.
"""

from __future__ import annotations

import json
import sys
import tempfile

from goworld_tpu.chaos.harness import run_chaos
from goworld_tpu.chaos.multigame import run_multigame
from goworld_tpu.config import read_config


def main() -> int:
    slo = read_config.get().slo
    summary: dict = {}
    for transport in ("tcp", "uds"):
        with tempfile.TemporaryDirectory(prefix="gw_chaos_") as d:
            r = run_chaos(d, transport=transport, slo=slo)
        with tempfile.TemporaryDirectory(prefix="gw_chaos_mg_") as d:
            try:
                mg = run_multigame(d, n_bots=12, transport=transport,
                                   with_restart_phase=True)
                r["scenarios"].append(mg["dispatcher_restart_phase"])
                r["passed"] += 1
            except Exception as exc:
                r["failures"].append({
                    "scenario": "migrate_during_dispatcher_restart",
                    "error": f"{type(exc).__name__}: {exc}"})
        summary[transport] = r
    print(json.dumps(summary, default=str))
    return 1 if any(r["failures"] for r in summary.values()) else 0


if __name__ == "__main__":
    sys.exit(main())
