"""Published peaks of one chip, keyed by JAX's ``device_kind``.  A device
that is not in the table is an error, never a default."""

from .manifest import load_json


def peaks_of(device_kind: str) -> dict:
    table = load_json("harness", "peaks.json")["devices"]
    if device_kind not in table:
        raise SystemExit(
            f"device kind {device_kind!r} is not in harness/peaks.json "
            f"(known: {sorted(table)}): add its published peaks with "
            f"their source before measuring on it")
    return table[device_kind]
