"""The table of device peaks (`peaks.json`), keyed by `device_kind`."""
import json
import os


def peaks_for(kind: str) -> dict:
    """The device's peaks; a kind the table does not hold is an error, never
    a default."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")) as fh:
        table = json.load(fh)["devices"]
    for key, row in table.items():
        if key.lower() in kind.lower():
            return row
    raise KeyError(f"device kind {kind!r} is not in benchmark/peaks.json")
