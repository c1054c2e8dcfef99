"""The plain reference of TableService's semantics: records as `bytes` in
a dict, read and written one op at a time. No JAX, no batching tricks.

`apply_batch(ops)` takes one batch of concurrent ops the way the service
linearises it: every update in arrival order, the later write to a
(key, field) winning, then every read.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Tuple


class TableReference:
    def __init__(self, records: Dict[int, bytes], field_count: int = 10):
        self.records = dict(records)
        self.field_count = field_count
        sizes = {len(r) for r in self.records.values()}
        if len(sizes) > 1 or any(s % field_count for s in sizes):
            raise ValueError(f"records must share one size that splits "
                             f"into {field_count} fields: {sorted(sizes)}")
        self.field_bytes = (sizes.pop() // field_count) if sizes else 0

    def read(self, key: int) -> bytes:
        return self.records[key]

    def update(self, key: int, field: int, value: bytes):
        if len(value) != self.field_bytes or not 0 <= field < \
                self.field_count:
            raise ValueError(f"field {field} of {len(value)} B")
        old = self.records[key]
        at = field * self.field_bytes
        self.records[key] = old[:at] + value + old[at + self.field_bytes:]

    def apply_batch(self, ops: Iterable[Tuple]) -> List[Optional[bytes]]:
        """ops: ("read", key) or ("update", key, field, value), in arrival
        order. Returns, per op, the record read (None for an update)."""
        ops = list(ops)
        for op in ops:
            if op[0] == "update":
                self.update(*op[1:])
        return [self.read(op[1]) if op[0] == "read" else None for op in ops]
