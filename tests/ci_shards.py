"""CI shard plan: the tier-1 suite split into parallel matrix groups.

The GitHub Actions matrix runs one pytest invocation per shard
(``python tests/ci_shards.py <shard>`` prints that shard's file list);
``--check`` verifies the union of the shards is exactly the set of
``tests/test_*.py`` files, so a new test file that nobody assigned to a
shard fails CI instead of silently never running.

Groups are balanced by observed runtime, not file count: the subprocess
distributed suites dominate, so they get their own shard (and run again on
the simulated 8-device mesh job, which exercises them with the mesh env).
"""

from __future__ import annotations

import glob
import os
import sys

SHARDS = {
    "kernels": [
        "tests/test_kernels_2d.py",
        "tests/test_kernels_3d.py",
        "tests/test_fused_run.py",
        "tests/test_padded_carry.py",
        "tests/test_temporal.py",
        "tests/test_temporal_variant.py",
        "tests/test_stencil_ref.py",
        "tests/test_program_ir.py",
        "tests/test_backends.py",
        "tests/test_properties.py",
        # compiles the kernels for a described v5e (TPU compiler, no chip)
        "tests/test_tpu_compile.py",
        "tests/test_bringup.py",
    ],
    "models-tuning": [
        "tests/test_obs.py",
        "tests/test_tuning.py",
        "tests/test_perf_model.py",
        "tests/test_roofline_parser.py",
        "tests/test_attention.py",
        "tests/test_mamba.py",
        "tests/test_moe.py",
        "tests/test_rwkv.py",
        "tests/test_models_smoke.py",
        "tests/test_optim.py",
        "tests/test_data.py",
        "tests/test_train_loop.py",
        "tests/test_checkpoint.py",
        "tests/test_fault.py",
        "tests/test_lint.py",
        # re-run standalone by the ci.yml dataflow job (like the
        # distributed shard rides mesh-sim), but assigned here exactly once
        "tests/test_dataflow.py",
        "tests/test_variant_api.py",
    ],
    "distributed": [
        "tests/test_distributed.py",
        "tests/test_sharded_fused.py",
        # the executor suite carries the host-mesh sharded-parity
        # subprocess, so it rides the mesh-sim shard like its peers
        "tests/test_executor.py",
    ],
}


def all_test_files() -> set:
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return {os.path.relpath(p, root).replace(os.sep, "/")
            for p in glob.glob(os.path.join(root, "tests", "test_*.py"))}


def check() -> int:
    """Exit non-zero when the shards and the test tree disagree."""
    sharded = [f for files in SHARDS.values() for f in files]
    dupes = {f for f in sharded if sharded.count(f) > 1}
    missing = all_test_files() - set(sharded)
    stale = set(sharded) - all_test_files()
    for label, bad in (("missing from every shard", missing),
                       ("assigned twice", dupes),
                       ("assigned but nonexistent", stale)):
        if bad:
            print(f"ci_shards: {label}: {sorted(bad)}", file=sys.stderr)
    return 1 if (missing or dupes or stale) else 0


def main(argv) -> int:
    if len(argv) != 1:
        print(f"usage: ci_shards.py [--check | {' | '.join(SHARDS)}]",
              file=sys.stderr)
        return 2
    if argv[0] == "--check":
        return check()
    if argv[0] not in SHARDS:
        print(f"unknown shard {argv[0]!r}; have {sorted(SHARDS)}",
              file=sys.stderr)
        return 2
    print(" ".join(SHARDS[argv[0]]))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
