"""Parameter-server throughput micro-benchmark.

Reference analog: the PS half of ``benchmarks/`` (SURVEY.md §3 C14):
send/receive round-trip latency and sustained one-way throughput against the
native shard servers, vs payload size and shard count.

Run: ``python benchmarks/ps_bench.py``
"""

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--sizes", type=str, default="65536,1048576,16777216")
    p.add_argument("--shards", type=int, default=4)
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--elastic", action="store_true",
                   help="also run an EASGD elastic-rule workload (the "
                        "response carries a full delta payload; its "
                        "bytes are tracked separately so the apply "
                        "ns/B denominator stays honest)")
    args = p.parse_args()

    from torchmpi_tpu.parallel.ps import ParameterServer

    for nbytes in (int(s) for s in args.sizes.split(",")):
        tree = {"p": np.zeros(nbytes // 4, np.float32)}
        ps = ParameterServer(tree, num_shards=args.shards)
        try:
            payload = {"p": np.ones(nbytes // 4, np.float32)}
            ps.send(payload, rule="add").wait()  # warm
            t0 = time.time()
            for _ in range(args.iters):
                ps.send(payload, rule="add").wait()
            send_dt = (time.time() - t0) / args.iters
            ps.receive().wait()
            t0 = time.time()
            for _ in range(args.iters):
                ps.receive().wait()
            recv_dt = (time.time() - t0) / args.iters
            # pipelined (async, wait at end) — the prefetch pattern's win
            t0 = time.time()
            hs = [ps.send(payload, rule="add") for _ in range(args.iters)]
            for h in hs:
                h.wait()
            pipe_dt = (time.time() - t0) / args.iters
            line = (f"{nbytes:>12d} B x{args.shards} shards  "
                    f"send {nbytes/send_dt/1e9:6.2f} GB/s  "
                    f"recv {nbytes/recv_dt/1e9:6.2f} GB/s  "
                    f"pipelined-send {nbytes/pipe_dt/1e9:6.2f} GB/s")
            if args.elastic:
                ps.send(payload, rule="elastic", alpha=0.5).wait()  # warm
                t0 = time.time()
                for _ in range(args.iters):
                    ps.send(payload, rule="elastic", alpha=0.5).wait()
                el_dt = (time.time() - t0) / args.iters
                # The elastic exchange moves the payload BOTH ways
                # (gradient in, delta out) — report the two-way rate.
                line += f"  elastic {2*nbytes/el_dt/1e9:6.2f} GB/s"
            print(line)
            # Server-loop cycle-cost decomposition (VERDICT r4 #8): the
            # measured split behind the loopback numbers — syscall
            # (recv+send) vs memcpy/rule-apply vs mutex contention.
            # The scaling model rests on these
            # constants: apply_ns/byte is the per-core shard-work floor,
            # recv/send the TCP stack share that a real NIC replaces.
            st = ps.stats()
            busy = st["recv_s"] + st["lock_wait_s"] + st["apply_s"] \
                + st["send_s"]
            if busy > 0 and st["ops"] > 0:
                def pct(x):
                    return f"{100.0 * x / busy:5.1f}%"

                # Bytes the apply bucket actually touched: send payloads
                # in + receive payloads out (bytes_out minus the 1-byte
                # status per op) — receives run their memcpy in `apply`
                # too (code review r5).  RULE_ELASTIC response payloads
                # are EXCLUDED (ADVICE round 5): the delta reply is
                # written into the same buffer the apply loop already
                # touched once as input, so counting it again would
                # inflate the ns/B denominator for elastic workloads —
                # the server tracks them separately (elastic_bytes_out).
                ebytes = st.get("elastic_bytes_out", 0)
                apply_bytes = (st["bytes_in"] + st["bytes_out"]
                               - st["ops"] - ebytes)
                line = (f"{'':>12s}   server-loop decomposition over "
                        f"{st['ops']} ops ({busy*1e3:.1f} ms busy): "
                        f"recv {pct(st['recv_s'])}  "
                        f"lock-wait {pct(st['lock_wait_s'])}  "
                        f"apply {pct(st['apply_s'])}  "
                        f"send {pct(st['send_s'])}  | "
                        f"apply {st['apply_s']*1e9/max(1,apply_bytes):.2f}"
                        f" ns/B")
                if ebytes:
                    line += f"  (elastic resp {ebytes} B excluded)"
                print(line)
        finally:
            ps.shutdown()


if __name__ == "__main__":
    main()
