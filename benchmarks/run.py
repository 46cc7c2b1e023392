"""Benchmark driver: one function per paper table + harness benches.

Prints ``name,us_per_call,derived`` CSV and writes a machine-readable
``BENCH_results.json`` (same rows plus parsed derived metrics, git rev, and
chip) so the perf trajectory is tracked PR-over-PR.  Paper-table modules
assert their reproduction tolerances, so ``python -m benchmarks.run``
doubles as the validation gate for the paper's own numbers.

Env knobs:
  REPRO_BENCH_TUNED=1   — kernel benches run from autotuned plans
                          (``repro.tuning``) instead of hand-written ones.
  REPRO_BENCH_JSON=PATH — where to write the JSON (default
                          ./BENCH_results.json; empty string disables).
  REPRO_BENCH_SMOKE=1   — fast subset (analytic tables + one small kernel
                          case); what CI runs per-PR to publish the
                          BENCH_results.json artifact.
  REPRO_BENCH_BACKEND   — pin the kernel-bench backend (see bench_kernels).
"""

import json
import os
import subprocess
import sys
import time


def _git_rev() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        ).stdout.strip() or "unknown"
    except Exception:
        return "unknown"


def _parse_derived(derived: str) -> dict:
    """'k=v;k=v' derived strings -> {k: float|str} (floats where they parse;
    trailing x/%% units stripped)."""
    out = {}
    for part in str(derived).split(";"):
        if "=" not in part:
            continue
        k, v = part.split("=", 1)
        try:
            out[k.strip()] = float(v.rstrip("x%"))
        except ValueError:
            out[k.strip()] = v
    return out


def main() -> None:
    from benchmarks import (bench_kernels, bench_step, fig34_trends,
                            roofline_table, table1_characteristics,
                            table3_perf_model, table45_roofline)
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    dev = jax.devices()[0]

    modules = [
        ("table1", table1_characteristics),
        ("table3", table3_perf_model),
        ("table45", table45_roofline),
        ("fig34", fig34_trends),
        ("kernels", bench_kernels),
        ("steps", bench_step),
        ("roofline", roofline_table),
    ]
    if os.environ.get("REPRO_BENCH_SMOKE") == "1":
        fast = {"table1", "table3", "kernels"}
        modules = [(n, m) for n, m in modules if n in fast]
    print("name,us_per_call,derived")
    results, errors = [], []
    for name, mod in modules:
        try:
            for row_name, us, derived in mod.run():
                print(f"{row_name},{us:.2f},{derived}")
                metrics = _parse_derived(derived)
                row = {
                    "name": row_name,
                    "suite": name,
                    "us_per_call": round(float(us), 3),
                    "derived": derived,
                    "metrics": metrics,
                }
                # model-accuracy telemetry rides as first-class row fields
                # so downstream consumers (check_regression, CI asserts)
                # need not re-parse the derived string
                if "model_accuracy" in metrics:
                    row["model_accuracy"] = metrics["model_accuracy"]
                if "bytes_accessed" in metrics:
                    row["bytes_accessed"] = int(metrics["bytes_accessed"])
                if isinstance(metrics.get("backend"), str):
                    row["backend"] = metrics["backend"]
                results.append(row)
        except Exception as e:  # pragma: no cover
            errors.append({"suite": name,
                           "error": f"{type(e).__name__}: {e}"})
            print(f"{name},ERROR,{type(e).__name__}:{e}", file=sys.stderr)

    json_path = os.environ.get("REPRO_BENCH_JSON", "BENCH_results.json")
    if json_path:
        payload = {
            "schema": 1,
            "git_rev": _git_rev(),
            "device": {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(jax.devices())},
            "tuned_plans": os.environ.get("REPRO_BENCH_TUNED") == "1",
            "smoke": os.environ.get("REPRO_BENCH_SMOKE") == "1",
            "backend": os.environ.get("REPRO_BENCH_BACKEND") or "default",
            "unix_time": int(time.time()),
            "results": results,
            "errors": errors,
        }
        with open(json_path, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
        print(f"wrote {json_path} ({len(results)} rows, "
              f"{len(errors)} errors)", file=sys.stderr)
    if errors:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
