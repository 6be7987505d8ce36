"""Source kind: an end-to-end utilization. The operations the forward
and backward passes need per sample (`cellbench/ops/<family>.py`, from
shapes, recompute not counted) times the rate the cell completed, over
chips times the peak in `cellbench/peaks.json`. Not a kernel's
roofline share, and it says nothing of idle time."""
import importlib
import json
import os


def peak(device_kind, what):
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "peaks.json")) as f:
        table = json.load(f)["device_kinds"]
    if device_kind not in table:
        raise KeyError("cellbench/peaks.json has no device kind %r"
                       % (device_kind,))
    return table[device_kind][what]


def read(readings, rate, ops="train_flops_per_sample"):
    if "e2e." + rate not in readings or "device_kind" not in readings:
        return None
    family = readings["cfg"]["family"]
    fn = getattr(importlib.import_module("cellbench.ops." + family), ops)
    need = fn(readings["cfg"], readings["traffic"])
    return 100.0 * need * readings["e2e." + rate] / (
        readings["chips"] * peak(readings["device_kind"],
                                 "bf16_flops_per_s"))
