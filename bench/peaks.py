"""Published peaks of a device, from ``peaks.json``, keyed by the
``device_kind`` JAX reports."""
import json
from pathlib import Path

TABLE = Path(__file__).resolve().parent / "peaks.json"


def lookup(kind: str, table: Path = TABLE) -> dict:
    """The peaks of ``kind``; a kind not in the table is an error."""
    peaks = json.loads(Path(table).read_text())
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in {table}; "
                       f"known: {sorted(peaks)}")
    return peaks[kind]
