"""Fleet process spawn on a TPU host: one chip per replica.

Run on the chip host, from the checkout root:

    python scripts/fleet_chip_check.py

It counts the host's chips as the supervisor does, starts that many
`spawn="process"` replicas (replica i pinned to chip i) over a seeded
store, checks that every routed count equals the host f64 oracle, and
checks that one replica more is refused with `ChipPlacementError` before
anything starts. This process stays on the CPU, so every chip is free for
a replica. The last stdout line is `{"ok": true, "chips": N}`.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

import jax

# before any backend use: the chips belong to the replicas
jax.config.update("jax_platforms", "cpu")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke as cs  # noqa: E402
from geomesa_tpu.cql import parse_cql  # noqa: E402
from geomesa_tpu.cql.hosteval import eval_filter_host  # noqa: E402
from geomesa_tpu.fleet.supervisor import (  # noqa: E402
    ChipPlacementError, FleetConfig, FleetSupervisor, local_tpu_chips)
from geomesa_tpu.fleet.wire import connect_json  # noqa: E402

ROWS = 1 << 16


def main() -> int:
    chips = local_tpu_chips()
    if chips < 1:
        raise SystemExit("fleet_chip_check: found no TPU chip on this host "
                         f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})")
    cs.log(f"fleet: {chips} TPU chip(s) on this host")
    with tempfile.TemporaryDirectory() as tmp:
        _, _, batch = cs.build_store(tmp, ROWS, seed=0)
        want = int(eval_filter_host(parse_cql(cs.CQL), batch).sum())
        sup = FleetSupervisor(FleetConfig(
            n_replicas=chips, catalog=tmp, spawn="process"))
        t = time.perf_counter()
        try:
            port = sup.start()
            cs.log(f"fleet: {chips} process replica(s) ready in "
                   f"{time.perf_counter() - t:.1f}s")
            cli = connect_json("127.0.0.1", port)
            got = [cli.request({"id": f"c{i}", "op": "count",
                                "typeName": cs.TYPE_NAME, "cql": cs.CQL,
                                "timeoutMs": 600_000}, timeout_s=900.0)
                   for i in range(2 * chips)]
            cli.close()
            states = sorted(r["state"] for r in sup.stats()["replicas"])
        finally:
            sup.close()
        cs.check(all(g.get("ok") and g["count"] == want for g in got),
                 f"routed counts {[g.get('count') for g in got]} == host "
                 f"oracle {want}")
        cs.check(states == ["ready"] * chips,
                 f"replica states {states}, one per chip")
        over = FleetSupervisor(FleetConfig(
            n_replicas=chips + 1, catalog=tmp, spawn="process"))
        try:
            over.start()
            refused = None
        except ChipPlacementError as e:
            refused = str(e)
        finally:
            over.close()
        cs.check(refused is not None and over.membership.all() == [],
                 f"{chips + 1} replicas refused before start: {refused}")
    print(json.dumps({"ok": True, "chips": chips}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
