"""Usage metering end to end: two tenants share a 3-replica decode
fleet, one replica is killed mid-run, and each tenant's bill is read
back FROM THE LEDGER — the durable JSONL file the meter appends one
immutable record per request to — through ``diagnose --format json``,
the same path an external billing job would use.

1. metering.start(path=...)   -> install the process meter + ledger
2. routed two-tenant load     -> the meter follows every request
3. kill one replica mid-run   -> failover replay billed exactly once
4. diagnose --format json     -> per-tenant bill + conservation verdict

    python examples/meter_tenants.py

The printed reconciliation verdict is the trust anchor: ``[OK]``
means the dual-entry books balance AND the meter's counters match the
router's own — the bill accounts for every admitted request.
"""
import json
import os
import subprocess
import sys
import tempfile
import time

import jax
import numpy as np

from mxnet_tpu import metering, telemetry
from mxnet_tpu.serving import DecodeServer, Router, ToyDecoderLM


def main():
    model = ToyDecoderLM(vocab=128, n_layers=2, n_heads=4,
                         head_dim=16, max_len=256)
    params = model.init_params(seed=0)

    def replica(i):
        srv = DecodeServer(model, params, seq_ladder=[32, 64],
                           max_new_tokens=12, window=8, page_size=16,
                           pool_pages=256, name="rep-%d" % i,
                           # one replica per device, round robin
                           device=jax.local_devices()[
                               i % jax.local_device_count()])
        srv.warmup()
        return srv

    with tempfile.TemporaryDirectory() as d:
        sink = os.path.join(d, "telemetry.jsonl")
        ledger = os.path.join(d, "usage.jsonl")
        telemetry.start(filename=sink, run_id="meter-demo")
        metering.start(name="fleet", path=ledger)

        router = Router([replica(i) for i in range(3)],
                        name="fleet", strikes=2,
                        tenants={"acme": {"weight": 2.0},
                                 "zeta": {"weight": 1.0}})
        rs = np.random.RandomState(0)
        try:
            reqs = []
            for i in range(12):
                prompt = rs.randint(1, 128, size=int(rs.randint(4, 24)))
                reqs.append(router.submit(
                    prompt, max_new_tokens=12,
                    tenant="acme" if i % 3 else "zeta"))
            # wait until streams are mid-flight, then kill a bound
            # replica: its sessions must fail over and their replay
            # tokens must land on the SURVIVOR's records, once
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                bound = [q._replica for q in reqs
                         if q._replica is not None and q.emitted]
                if bound:
                    victim = bound[0]
                    print("killing %s mid-run" % victim.name)
                    victim.kill()
                    break
                time.sleep(0.002)
            for q in reqs:
                q.result(timeout=120)
            st = router.stats()
        finally:
            router.stop()
        metering.stop()
        telemetry.stop()

        # the bill, read back from the ledger the way a billing job
        # would: diagnose renders the raw usage_record lines
        out = subprocess.run(
            [sys.executable, "-m", "mxnet_tpu.tools.diagnose",
             ledger, "--format", "json"],
            check=True, capture_output=True, text=True)
        usage = json.loads(out.stdout)["usage"]["ledger"]
        print("\nper-tenant bill (from %s):" % ledger)
        for name, t in sorted(usage["tenants"].items()):
            print("  %-5s: %4d prompt + %4d generated tok, "
                  "%6.3f KV page*s, %d replayed on failover, "
                  "outcomes %s"
                  % (name, t["prompt_tokens"], t["generated_tokens"],
                     t["page_seconds"], t["replay_tokens"],
                     t["outcomes"]))

        # the conservation verdict rides the telemetry run: the
        # meter's final `usage` record cross-checked vs the router
        out = subprocess.run(
            [sys.executable, "-m", "mxnet_tpu.tools.diagnose",
             sink, "--format", "json"],
            check=True, capture_output=True, text=True)
        fleet = json.loads(out.stdout)["usage"]["fleet"]
        verdict = "OK" if fleet["reconciled"] else "MISMATCH"
        print("\nrouter: %d requests, %d failover(s), %d replay tok"
              % (st["requests"], st["failovers"], st["replay_tokens"]))
        print("meter : %d billed, %d replay tok"
              % (fleet["closed"], fleet["totals"]["replay_tokens"]))
        print("reconciliation: [%s] (%d checks)"
              % (verdict, len(fleet["reconcile_checks"])))
        if not fleet["reconciled"]:
            raise SystemExit(1)


if __name__ == "__main__":
    main()
