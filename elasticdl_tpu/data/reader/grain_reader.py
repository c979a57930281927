"""Grain dataset adapter: any random-access `grain.MapDataset` (or plain
sequence) becomes shard-addressable.

SURVEY §7 notes Grain's `elastic_iterator` as directly relevant to the
rebuild; in this framework the ELASTICITY side of that problem is already
owned by the master's task queue (shards re-lease on membership change, no
deterministic re-split needed), so the adapter only needs Grain's
random-access contract: `len(ds)` + `ds[i]`.  Records can be whatever the
zoo `feed` understands (bytes, dicts, arrays) — Grain transforms
(`.map`, `.shuffle(seed)`, mixtures) compose upstream of the factory.

Origin format:  grain://dotted.module:factory[?k=v&k2=v2]
The factory resolves like a zoo `--model_def` (model_zoo is on sys.path),
is called with the parsed query kwargs (ast.literal_eval'd — literals
only, never code), and must return a random-access dataset.
"""

from __future__ import annotations

import ast
import importlib
from typing import Iterator, List, Tuple
from urllib.parse import parse_qsl, urlparse

from elasticdl_tpu.data.reader.base import AbstractDataReader


def _resolve(origin: str):
    if not origin.startswith("grain://"):
        origin = "grain://" + origin
    parsed = urlparse(origin)
    target = (parsed.netloc + parsed.path).strip("/")
    module_path, _, fn_name = target.partition(":")
    if not fn_name:
        raise ValueError(
            f"grain origin must be grain://module.path:factory, got "
            f"{origin!r}"
        )
    module = importlib.import_module(module_path)
    factory = getattr(module, fn_name)
    kwargs = {}
    for key, value in parse_qsl(parsed.query):
        try:
            kwargs[key] = ast.literal_eval(value)
        except (ValueError, SyntaxError):
            kwargs[key] = value  # raw string
    return factory(**kwargs)


class GrainDataReader(AbstractDataReader):
    """Shard-addressable reader over a Grain MapDataset factory."""

    def __init__(self, data_dir: str = "", records_per_shard: int = 0,
                 **kwargs):
        # data_dir: origin with or without the grain:// prefix (the
        # registry strips the scheme before construction)
        super().__init__(**kwargs)
        self._origin = data_dir
        self._records_per_shard = records_per_shard
        self._dataset = None

    @property
    def dataset(self):
        if self._dataset is None:
            self._dataset = _resolve(self._origin)
        return self._dataset

    def read_records(self, task) -> Iterator:
        ds = self.dataset
        end = min(task.shard.end, len(ds))
        for i in range(task.shard.start, end):
            yield ds[i]

    def create_shards(self) -> List[Tuple[str, int, int]]:
        n = len(self.dataset)
        per = self._records_per_shard or n
        return [
            (self._origin, start, min(start + per, n))
            for start in range(0, n, per)
        ]
