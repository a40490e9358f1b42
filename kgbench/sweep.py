"""Find an open-loop cell's knee on the chip: serve its traffic at a series
of fixed rates, one short window each, in one process.

  python3 kgbench/sweep.py lubm-zipf-open 30 4.5 5 5.5 6

Arguments: the cell, the window's seconds, then the rates in queries/s.
Each rate serves the cell's own traffic file with only ``rate_qps``
changed, so its arrivals follow the same rule as the cell's. For each rate
it prints one JSON line: latency p50 and p95 from the due time, answered
queries/s inside the window, how late the client ran, and the backlog
(requests due but not answered) when the window closed. The knee is the
highest rate whose backlog stays near zero; a cell is set at about four
fifths of it. Set-up, warm-up and correctness are as in run.py.
"""
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def window(dep, cell, mix, schedule, seconds: float) -> dict:
    """Serve `schedule`, open-loop requests of `mix`, for one window;
    drain, check every answer, and summarise the window as one dict."""
    from kgbench import harness, stats
    srv = dep.server
    srv.reset_stats()
    reqs, t0, t1 = harness.serve_window(dep, mix, schedule, seconds)
    backlog = sum(1 for r in reqs if not r.ticket.done)
    srv.drain()
    drain_s = srv.pipeline.clock() - t1
    chk = harness.check(dep, cell, reqs, reqs)
    lat = [float("inf") if id(r) in chk.bad else
           (r.ticket.t_done - r.due) * 1e3 for r in reqs]
    done_in = sum(1 for r in reqs if id(r) not in chk.bad
                  and r.ticket.t_done <= t0 + seconds)
    return {"offered_qps": len(reqs) / seconds, "requests": len(reqs),
            "latency_p50_ms": stats.percentile(lat, 50),
            "latency_p95_ms": stats.percentile(lat, 95),
            "answered_qps": done_in / seconds,
            "client_lag_p95_ms": stats.percentile(
                [(r.submit - r.due) * 1e3 for r in reqs], 95),
            "backlog_at_close": backlog, "drain_s": drain_s,
            "correct": chk.correct, "stats": srv.stats}


def main() -> int:
    from kgbench import harness
    from kgbench.traffic import Mix

    cell = harness.resolve(sys.argv[1], ROOT)
    seconds = float(sys.argv[2])
    rates = [float(r) for r in sys.argv[3:]]
    harness.device_info(cell.chips, require_tpu=True)
    harness.enable_compile_cache()
    t = time.monotonic()
    dep = harness.build(cell, 1)
    harness.warmup(dep)
    print(f"set-up {time.monotonic() - t:.2f} s", file=sys.stderr,
          flush=True)
    doms = harness.domains(cell, dep.graph)
    for k, rate in enumerate(rates):
        mix = Mix(dict(cell.traffic, rate_qps=rate), doms, 1000 + k)
        out = window(dep, cell, mix, mix.open_schedule(seconds), seconds)
        print(json.dumps({"rate_qps": rate, **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
