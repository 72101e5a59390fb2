"""Failure-tagging and baseline-normalization behavior of bench.py:
  * a run that was asked for the device and finds none fails (no CPU
    number under a device metric's name)
  * failed subprocess results are tagged, never silently used as headline
  * vs_baseline is param-normalized (the reference's 51.22 tok/s/GPU is a
    70B-model example — docs/benchmarks/pre_deployment_profiling.md:56)
"""

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import bench  # noqa: E402


def test_baseline_ratio_param_normalized():
    # 51.22 tok/s of a 70B model is the reference point: ratio 1.0
    assert bench.baseline_ratio(51.22, "llama3-70b") == 1.0
    # a 3.2B model must clear 70/3.2 x the tok/s for the same ratio
    r3b = bench.baseline_ratio(51.22 * 70 / 3.2, "llama3-3b")
    assert abs(r3b - 1.0) < 0.01
    # unknown models produce None, not a bogus ratio
    assert bench.baseline_ratio(100.0, "unknown-model") is None


def test_non_smoke_bench_without_a_tpu_fails():
    # the suite runs on the CPU: --smoke is allowed there, a measurement
    # of the chip is not, and exits non-zero with no result line
    import pytest

    bench.require_tpu(smoke=True)
    with pytest.raises(SystemExit) as e:
        bench.require_tpu(smoke=False)
    assert e.value.code not in (0, None) and "no TPU" in str(e.value.code)


def test_tag_error_marks_failed_results():
    line = json.dumps({"metric": "m", "value": 1.0})
    tagged = json.loads(bench._tag_error(line, 3))
    assert tagged["error"] == "bench_exit_3"
    assert tagged["value"] == 1.0
    # non-JSON passes through untouched rather than raising
    assert bench._tag_error("not json", 1) == "not json"


def test_json_lines_reports_returncode():
    line, rc = bench._json_lines(
        [sys.executable, "-c", "print('{\"metric\": \"x\"}'); raise SystemExit(7)"],
        "t",
    )
    assert rc == 7
    assert json.loads(line)["metric"] == "x"
