"""The comparison that decides `correct`: every response of the window
against the plain reference, by decoded pixels."""

import concurrent.futures
import io

import numpy as np
from PIL import Image


def judge(sample: dict, want: np.ndarray) -> dict:
    """{'good', 'why', 'max_abs_diff'} of one response."""
    if sample["error"] is not None:
        return {"good": False, "why": "unanswered", "max_abs_diff": None}
    if sample["status"] != 200:
        return {"good": False, "why": "non_200", "max_abs_diff": None}
    if sample["degraded"]:
        return {"good": False, "why": "degraded", "max_abs_diff": None}
    try:
        got = np.array(Image.open(io.BytesIO(sample["body"])))
    except Exception:
        return {"good": False, "why": "undecodable", "max_abs_diff": None}
    if got.shape != want.shape:
        return {"good": False, "why": "wrong_pixels", "max_abs_diff": None}
    diff = int(np.max(np.abs(got.astype(np.int64) - want.astype(np.int64))))
    return {"good": diff == 0, "why": None if diff == 0 else "wrong_pixels",
            "max_abs_diff": diff}


def judge_all(samples: list, data, expected, workers: int = 8) -> None:
    """Sets sample['good'], ['why'], ['max_abs_diff'] on every sample
    (PIL's inflate releases the GIL, so a few threads help)."""
    def one(sample):
        sample.update(judge(sample, expected(data, sample["request"])))

    with concurrent.futures.ThreadPoolExecutor(workers) as pool:
        list(pool.map(one, samples))


REASONS = ("unanswered", "non_200", "degraded", "undecodable",
           "wrong_pixels")


def checks(samples: list, fallback_lanes, engine_ok: bool) -> dict:
    """Every number compared, beside its limit (all exact: limit 0)."""
    diffs = [s["max_abs_diff"] for s in samples
             if s.get("max_abs_diff") is not None]
    out = {
        why: {"value": sum(1 for s in samples if s.get("why") == why),
              "limit": 0}
        for why in REASONS
    }
    out["max_abs_pixel_diff"] = {"value": max(diffs) if diffs else 0,
                                 "limit": 0}
    out["compared"] = {"value": len(diffs), "limit_min": 1}
    if fallback_lanes is not None:
        out["fallback_lanes"] = {"value": fallback_lanes, "limit": 0}
    out["engine_not_device"] = {"value": 0 if engine_ok else 1, "limit": 0}
    return out


def verdict(numbers: dict) -> bool:
    for entry in numbers.values():
        if "limit" in entry and entry["value"] > entry["limit"]:
            return False
        if "limit_min" in entry and entry["value"] < entry["limit_min"]:
            return False
    return True
