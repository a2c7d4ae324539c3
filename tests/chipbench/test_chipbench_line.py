"""The contract of the last line (chipbench/check_line.py): lines that pass
and each way a traced line goes bad on this program."""

import json

import pytest

from chipbench import check_line

E2E = {"ttft_p90_ms": "ms", "setup_s": "s"}
LAYER = {"decode_step_ms": "ms", "device_idle.chat": "%", "serve.mfu": "%"}


def line(traced=False, **patch):
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1, "memory_peak_bytes": 13958643712}
    out = {"correct": True, "attempted": 400, "failed": 0}
    if traced:
        out["metrics"] = {"decode_step_ms": {"value": 17.5, "unit": "ms"},
                          "device_idle.chat": {"value": 21.0, "unit": "%"},
                          "serve.mfu": {"value": 3.5, "unit": "%"}}
        dev.update(busy_s=2.1, window_s=3.05)
        out["device"] = dev
        out["breakdown"] = {"device_ops": [["fusion.1", 0.5]], "idle_gaps": [["host_idle", 0.2]]}
    else:
        out["metrics"] = {"ttft_p90_ms": {"value": 212.4071, "unit": "ms"}, "setup_s": {"value": 95.3127, "unit": "s"}}
        out["device"] = dev
    out["compared"] = {"gap_max": {"value": 0.1, "limit": 0.5}}
    for path, value in patch.items():
        node, keys = out, path.split("/")
        for k in keys[:-1]:
            node = node[k]
        if value is ...:
            del node[keys[-1]]
        else:
            node[keys[-1]] = value
    return out


@pytest.mark.parametrize("traced", [False, True])
def test_good_line_passes(traced):
    units = LAYER if traced else E2E
    assert check_line.problems(json.dumps(line(traced)), units, 1, traced, "tpu") == []


BAD_TRACED = {
    "busy_over_window": {"device/busy_s": 3.2},
    "busy_zero": {"device/busy_s": 0.0},
    "busy_missing": {"device/busy_s": ...},
    "window_missing": {"device/window_s": ...},
    "metric_missing": {"metrics/decode_step_ms": ...},
    "metric_null": {"metrics/decode_step_ms/value": None},
    "metric_bool": {"metrics/decode_step_ms/value": True},
    "wrong_unit": {"metrics/decode_step_ms/unit": "us"},
    "unit_with_space": {"metrics/device_idle.chat/unit": "per cent"},
    "share_over_105": {"metrics/serve.mfu/value": 140.0},
    "share_zero": {"metrics/serve.mfu/value": 0.0},
    "count_mismatch": {"device/count": 4},
    "platform_cpu": {"device/platform": "cpu"},
    "no_memory_peak": {"device/memory_peak_bytes": 0},
    "failed_over_attempted": {"failed": 401},
    "correct_not_bool": {"correct": "true"},
    "breakdown_too_long": {"breakdown/device_ops": [["op", 0.1]] * 11},
    "key_missing": {"attempted": ...},
}


@pytest.mark.parametrize("name", sorted(BAD_TRACED))
def test_bad_traced_line_is_refused(name):
    text = json.dumps(line(True, **BAD_TRACED[name]))
    assert check_line.problems(text, LAYER, 1, True, "tpu"), name


@pytest.mark.parametrize("token", ["NaN", "Infinity", "-Infinity"])
def test_non_finite_numbers_are_refused(token):
    text = json.dumps(line(True)).replace("17.5", token)
    assert check_line.problems(text, LAYER, 1, True, "tpu")
    with pytest.raises(ValueError):
        json.dumps({"x": float(token.replace("Infinity", "inf"))}, allow_nan=False)


def test_output_after_the_line_is_refused():
    assert check_line.problems(json.dumps(line()) + "\nbye", E2E, 1, False, "tpu")


def test_untraced_line_may_not_carry_trace_readings():
    assert check_line.problems(json.dumps(line(False, **{"device/busy_s": 1.0})), E2E, 1, False, "tpu")


def test_compared_comes_last():
    out = line()
    out["device"] = out.pop("device")  # now after compared
    assert check_line.problems(json.dumps(out), E2E, 1, False, "tpu")
