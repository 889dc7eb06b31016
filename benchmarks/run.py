"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV, and appends each module's rows to
its trajectory file ``benchmarks/BENCH_<name>.json`` (timestamped records
— tok/s, bytes moved — so perf PRs land against a recorded baseline; see
_record.py).

  Table 1/2 (energy)      -> bench_energy
  Table 3  (test error)   -> bench_accuracy
  Fig. 1   (convergence)  -> bench_convergence
  Fig. 2 / §4.2 (kernels) -> bench_kernel_dedup
  Fig. 4   (saturation)   -> bench_saturation
  binary GEMM kernel      -> bench_binary_gemm
  §6 deployment (packed)  -> bench_packed_serving
  continuous batching     -> bench_continuous_serving (slot scheduler vs
                             static same-length batches, mixed traffic)
  bit-resident chain      -> bench_bit_resident (fused packed-I/O epilogue
                             vs unfused: HBM bytes + wall time per layer)
  packed KV decode attn   -> bench_decode_attention (bit-resident KV cache:
                             resident bytes + bytes/step vs float cache)
  chunked prefill         -> bench_prefill_interleave (chunked admission
                             interleaved with decode bursts vs whole-prompt
                             head-of-line blocking: inter-token p99, TTFT,
                             admission stall, compile counts)
  paged KV + prefix cache -> bench_prefix_cache (radix-tree prefix sharing
                             over the paged packed pool vs contiguous
                             chunked: prefill tokens saved, TTFT, pool
                             bytes packed vs float)
  fault-tolerant serving  -> bench_resilience (goodput + shed/error
                             accounting under a deterministic fault
                             schedule: burst errors retried, poisoned
                             admission isolated, exhaustion requeued,
                             corruption degraded — survivors bit-identical
                             to the fault-free run)
  mesh-sharded serving    -> bench_sharded_serving (slot batch sharded over
                             a device mesh: modeled tok/s scaling,
                             bytes/device from real shards, replica fit —
                             runs its measurement in a subprocess with
                             forced host devices)
  roofline (dry-run)      -> src/repro/roofline/report.py (separate: needs
                             the 512-device dryrun_results.jsonl)
"""
from __future__ import annotations

import os
import sys

# allow `python benchmarks/run.py` from the repo root: the `benchmarks`
# package itself must be importable
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    from benchmarks import (
        bench_accuracy, bench_binary_gemm, bench_bit_resident,
        bench_continuous_serving, bench_convergence, bench_decode_attention,
        bench_energy, bench_kernel_dedup, bench_packed_serving,
        bench_prefill_interleave, bench_prefix_cache, bench_resilience,
        bench_saturation, bench_sharded_serving,
    )
    from benchmarks._record import record
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    mods = [bench_energy, bench_binary_gemm, bench_packed_serving,
            bench_continuous_serving, bench_prefill_interleave,
            bench_prefix_cache, bench_resilience, bench_sharded_serving,
            bench_bit_resident,
            bench_decode_attention, bench_kernel_dedup, bench_accuracy,
            bench_saturation, bench_convergence]
    # these record their own trajectory entries (rows + structured extras),
    # standalone or under run.py — don't double-append
    self_recording = {bench_bit_resident, bench_decode_attention,
                      bench_packed_serving, bench_continuous_serving,
                      bench_prefill_interleave, bench_prefix_cache,
                      bench_resilience, bench_sharded_serving}
    only = sys.argv[1] if len(sys.argv) > 1 else None
    print("name,us_per_call,derived")
    for mod in mods:
        if only and only not in mod.__name__:
            continue
        rows = mod.run()
        name = mod.__name__.rsplit(".", 1)[-1].removeprefix("bench_")
        if mod not in self_recording:
            record(name, rows)
        for row_name, us, derived in rows:
            print(f"{row_name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()
