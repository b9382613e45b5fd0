"""Core data model for the per-partition engine.

TPU-native re-design of the reference's table/space schema
(reference: internal/entity/space.go:75 `Space`, internal/engine/c_api/api_data/table.h:44
`TableInfo`, internal/ps/engine/mapping/field.go field types).
"""

from __future__ import annotations

import enum
from dataclasses import dataclass, field
from typing import Any


class DataType(enum.Enum):
    """Field data types (reference: internal/engine/idl/fbs/types.fbs DataType)."""

    INT = "integer"
    LONG = "long"
    FLOAT = "float"
    DOUBLE = "double"
    STRING = "string"
    STRING_ARRAY = "stringArray"
    DATE = "date"
    VECTOR = "vector"
    BOOL = "bool"


class MetricType(enum.Enum):
    """Distance metrics (reference: index params `metric_type` L2/InnerProduct)."""

    L2 = "L2"
    INNER_PRODUCT = "InnerProduct"
    COSINE = "Cosine"


class IndexStatus(enum.IntEnum):
    """Index build state machine (reference: search/engine.h:28-33 IndexingState
    IDLE/STARTING/RUNNING/STOPPING plus engine_status INDEXED)."""

    UNINDEXED = 0
    TRAINING = 1
    INDEXING = 2
    INDEXED = 3


class ScalarIndexType(enum.Enum):
    """Scalar index flavours (reference: table/scalar_index.h:28 + inverted/bitmap/composite)."""

    NONE = "NONE"
    INVERTED = "INVERTED"
    BITMAP = "BITMAP"


@dataclass
class IndexParams:
    """Vector index configuration.

    Mirrors the reference's per-field `index` block in a space schema
    (reference: sdk/python/vearch/schema/index.py, entity/space.go index params):
    index_type one of FLAT / IVFFLAT / IVFPQ / HNSW / BINARYIVF / IVFRABITQ,
    plus params (nlist/nprobe/m/nbits/efConstruction/efSearch/training_threshold).
    """

    index_type: str = "FLAT"
    metric_type: MetricType = MetricType.L2
    params: dict[str, Any] = field(default_factory=dict)

    def get(self, key: str, default: Any = None) -> Any:
        return self.params.get(key, default)

    def to_dict(self) -> dict[str, Any]:
        return {
            "index_type": self.index_type,
            "metric_type": self.metric_type.value,
            "params": dict(self.params),
        }

    @classmethod
    def from_dict(cls, d: dict[str, Any]) -> "IndexParams":
        return cls(
            index_type=d.get("index_type", "FLAT"),
            metric_type=MetricType(d.get("metric_type", "L2")),
            params=dict(d.get("params", {})),
        )


@dataclass
class FieldSchema:
    """One field of a table (reference: entity/space.go `SpaceProperties`,
    mapping/field.go `FieldMapping`)."""

    name: str
    data_type: DataType
    dimension: int = 0  # for VECTOR fields
    index: IndexParams | None = None  # vector index or scalar index request
    scalar_index: ScalarIndexType = ScalarIndexType.NONE

    def is_vector(self) -> bool:
        return self.data_type is DataType.VECTOR

    @property
    def wire_dim(self) -> int:
        """Vector length on the wire: binary indexes pack 8 bits per
        uint8 byte (reference: faiss binary vector format)."""
        if self.index and self.index.index_type.upper() == "BINARYIVF":
            return self.dimension // 8
        return self.dimension

    def to_dict(self) -> dict[str, Any]:
        return {
            "name": self.name,
            "data_type": self.data_type.value,
            "dimension": self.dimension,
            "index": self.index.to_dict() if self.index else None,
            "scalar_index": self.scalar_index.value,
        }

    @classmethod
    def from_dict(cls, d: dict[str, Any]) -> "FieldSchema":
        return cls(
            name=d["name"],
            data_type=DataType(d["data_type"]),
            dimension=d.get("dimension", 0),
            index=IndexParams.from_dict(d["index"]) if d.get("index") else None,
            scalar_index=ScalarIndexType(d.get("scalar_index", "NONE")),
        )


@dataclass
class TableSchema:
    """Per-partition table schema (reference: api_data/table.h:44 `TableInfo`).

    `training_threshold`: docs required before background index build starts
    (reference: engine.cc:966 BuildIndex threshold check).
    `refresh_interval_ms`: realtime indexing loop cadence
    (reference: engine.cc:1146 sleep between AddRTVecsToIndex passes).
    """

    name: str
    fields: list[FieldSchema]
    training_threshold: int = 0
    refresh_interval_ms: int = 1000
    # multi-column equality indexes (reference: composite_index.h)
    composite_indexes: list[list[str]] = field(default_factory=list)

    def vector_fields(self) -> list[FieldSchema]:
        return [f for f in self.fields if f.is_vector()]

    def scalar_fields(self) -> list[FieldSchema]:
        return [f for f in self.fields if not f.is_vector()]

    def field(self, name: str) -> FieldSchema:
        for f in self.fields:
            if f.name == name:
                return f
        raise KeyError(f"no field named {name!r}")

    def to_dict(self) -> dict[str, Any]:
        return {
            "name": self.name,
            "fields": [f.to_dict() for f in self.fields],
            "training_threshold": self.training_threshold,
            "refresh_interval_ms": self.refresh_interval_ms,
            "composite_indexes": self.composite_indexes,
        }

    @classmethod
    def from_dict(cls, d: dict[str, Any]) -> "TableSchema":
        return cls(
            name=d["name"],
            fields=[FieldSchema.from_dict(f) for f in d["fields"]],
            training_threshold=d.get("training_threshold", 0),
            refresh_interval_ms=d.get("refresh_interval_ms", 1000),
            composite_indexes=[list(c) for c in d.get("composite_indexes", [])],
        )


@dataclass
class SearchResultItem:
    """One hit: doc key, score, optional fields payload, and — when the
    request carried a `sort` spec — the hit's sort values in spec order
    (reference: response/doc_results.go SortValues; the router merges on
    these without re-deriving them from fields)."""

    key: str
    score: float
    fields: dict[str, Any] = field(default_factory=dict)
    sort_values: list | None = None


@dataclass
class SearchResult:
    """Per-query result list (reference: api_data/response.h:56 `Response`)."""

    items: list[SearchResultItem] = field(default_factory=list)


@dataclass
class ColumnarSearchResults:
    """Fields-free search results in columnar form: every hit's key in
    one flat list (query by query), the hits of each query row, and ONE
    flat score buffer. Returned by Engine.search for `raw_results`
    requests — building b*k SearchResultItem objects measured ~50 ms of
    host time at b=1024, which a TPU-speed kernel cannot hide; the PS
    packs this shape into the reply's arrays (cluster/hitarrays.py)."""

    flat_keys: list[str]
    counts: Any  # np.ndarray [queries] int
    scores: Any  # np.ndarray [len(flat_keys)] f32

    @property
    def keys(self) -> list[list[str]]:
        """Per-query key lists (the shadow sampler's and tests' view)."""
        out, lo = [], 0
        for n in self.counts.tolist():
            out.append(self.flat_keys[lo:lo + n])
            lo += n
        return out
