"""One number compared, beside its limit."""

from __future__ import annotations

from typing import Dict


class Check:
    def __init__(self, name: str, value: float, limit: float) -> None:
        self.name = name
        self.value = float(value)
        self.limit = float(limit)

    @property
    def ok(self) -> bool:
        return self.value <= self.limit  # a NaN fails

    def to_json(self) -> Dict[str, float]:
        return {"value": self.value, "limit": self.limit}
