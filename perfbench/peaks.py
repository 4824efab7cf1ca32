"""Published peaks of each chip, keyed by JAX's `device_kind`
(`peaks.json`, which names its source). A chip that is not in the table is
an error, never a default."""
from __future__ import annotations

import json
import pathlib

TABLE = pathlib.Path(__file__).with_name("peaks.json")


class UnknownDevice(KeyError):
    pass


def peak(device_kind: str, table: pathlib.Path = TABLE) -> dict:
    peaks = json.loads(table.read_text())
    if device_kind not in peaks:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r} in {table.name}")
    return peaks[device_kind]
