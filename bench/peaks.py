"""The chip's published peaks (``bench/peaks.json``), keyed by the
``device_kind`` JAX reports.  A device not in the table is an error."""
import json
from pathlib import Path


def peaks(kind: str) -> dict:
    table = json.loads((Path(__file__).with_name("peaks.json"))
                       .read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no published peaks for device kind {kind!r} in "
                       f"bench/peaks.json (known: {', '.join(table)})")
    return table[kind]
