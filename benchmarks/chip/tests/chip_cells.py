"""A tiny cell for the tests: a qwen3-shaped decoder, with limits set from
CPU readings at this size: the program's readings under each limit, the
control's above those that ``test_chip_control`` checks."""
import json
import os
import time

from chipbench import harness

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(rel):
    with open(os.path.join(CHIP, rel)) as f:
        return json.load(f)


def spec(config: dict = None) -> dict:
    return {"cell": {"name": "tiny.train", "chips": 1},
            "config": config or _load("tests/data/tiny-lm.json"),
            "traffic": _load("tests/data/train.tiny.json"),
            "limits": _load("tests/data/limits.tiny.json"),
            "end_to_end": ["train_tokens_per_s", "setup_s"], "per_layer": [],
            "units": {"train_tokens_per_s": "tokens/s", "setup_s": "s"}}


def bench(seed: int, config: dict = None) -> harness.Bench:
    return harness.Bench(spec(config), seed, 0.0, False, time.monotonic())


def driver():
    return harness.load_module(os.path.join(CHIP, "drivers", "train.py"))


def reference():
    return harness.load_module(os.path.join(CHIP, "reference", "qwen3.py"))
