"""Typed accessors over the flat hyperspace.* config namespace.

Reference parity: util/HyperspaceConf.scala:27-220 (typed getters with
validation and legacy-key fallback) over session-level runtime-mutable conf.
"""

from __future__ import annotations

from typing import Any, Mapping

from . import constants as C
from .exceptions import HyperspaceError


class HyperspaceConf:
    """Wraps a session conf dict; all getters read live values so settings are
    runtime-mutable per session like Spark's SQLConf."""

    def __init__(self, conf: Mapping[str, Any]):
        self._conf = conf

    def _get(self, key: str, default: Any) -> Any:
        return self._conf.get(key, default)

    def get(self, key: str, default: Any = None) -> Any:
        """Public raw accessor for keys without a typed getter."""
        return self._conf.get(key, default)

    @staticmethod
    def _as_bool(v: Any) -> bool:
        if isinstance(v, bool):
            return v
        return str(v).strip().lower() in ("true", "1", "yes")

    # --- toggles ---
    @property
    def apply_enabled(self) -> bool:
        return self._as_bool(self._get(C.APPLY_ENABLED, C.APPLY_ENABLED_DEFAULT))

    @property
    def default_source_formats(self) -> tuple[str, ...]:
        """Formats the default file-based source accepts (conf-gated, ref:
        HyperspaceConf.supportedFileFormatsForDefaultFileBasedSource)."""
        raw = str(
            self._get(C.DEFAULT_SOURCE_FORMATS, C.DEFAULT_SOURCE_FORMATS_DEFAULT)
        )
        return tuple(p.strip().lower() for p in raw.split(",") if p.strip())

    @property
    def hybrid_scan_enabled(self) -> bool:
        return self._as_bool(
            self._get(C.HYBRID_SCAN_ENABLED, C.HYBRID_SCAN_ENABLED_DEFAULT)
        )

    @property
    def hybrid_scan_max_appended_ratio(self) -> float:
        v = float(
            self._get(
                C.HYBRID_SCAN_MAX_APPENDED_RATIO,
                C.HYBRID_SCAN_MAX_APPENDED_RATIO_DEFAULT,
            )
        )
        if not 0.0 <= v <= 1.0:
            raise HyperspaceError(f"{C.HYBRID_SCAN_MAX_APPENDED_RATIO} must be in [0,1]: {v}")
        return v

    @property
    def hybrid_scan_max_deleted_ratio(self) -> float:
        v = float(
            self._get(
                C.HYBRID_SCAN_MAX_DELETED_RATIO,
                C.HYBRID_SCAN_MAX_DELETED_RATIO_DEFAULT,
            )
        )
        if not 0.0 <= v <= 1.0:
            raise HyperspaceError(f"{C.HYBRID_SCAN_MAX_DELETED_RATIO} must be in [0,1]: {v}")
        return v

    @property
    def lineage_enabled(self) -> bool:
        return self._as_bool(
            self._get(C.INDEX_LINEAGE_ENABLED, C.INDEX_LINEAGE_ENABLED_DEFAULT)
        )

    @property
    def filter_rule_use_bucket_spec(self) -> bool:
        return self._as_bool(
            self._get(
                C.FILTER_RULE_USE_BUCKET_SPEC, C.FILTER_RULE_USE_BUCKET_SPEC_DEFAULT
            )
        )

    # --- covering ---
    @property
    def num_buckets(self) -> int:
        # Legacy-key fallback (ref: HyperspaceConf.numBucketsForIndex:88-93).
        v = self._conf.get(C.INDEX_NUM_BUCKETS)
        if v is None:
            v = self._conf.get(C.INDEX_NUM_BUCKETS_LEGACY, C.INDEX_NUM_BUCKETS_DEFAULT)
        n = int(v)
        if n <= 0:
            raise HyperspaceError(f"{C.INDEX_NUM_BUCKETS} must be positive: {n}")
        return n

    # --- optimize ---
    @property
    def optimize_file_size_threshold(self) -> int:
        return int(
            self._get(
                C.OPTIMIZE_FILE_SIZE_THRESHOLD, C.OPTIMIZE_FILE_SIZE_THRESHOLD_DEFAULT
            )
        )

    # --- cache ---
    @property
    def cache_expiry_seconds(self) -> int:
        return int(
            self._get(C.INDEX_CACHE_EXPIRY_SECONDS, C.INDEX_CACHE_EXPIRY_SECONDS_DEFAULT)
        )

    # --- z-order ---
    @property
    def zorder_target_source_bytes_per_partition(self) -> int:
        return int(
            self._get(
                C.ZORDER_TARGET_SOURCE_BYTES_PER_PARTITION,
                C.ZORDER_TARGET_SOURCE_BYTES_PER_PARTITION_DEFAULT,
            )
        )

    @property
    def zorder_quantile_enabled(self) -> bool:
        return self._as_bool(
            self._get(C.ZORDER_QUANTILE_ENABLED, C.ZORDER_QUANTILE_ENABLED_DEFAULT)
        )

    @property
    def zorder_quantile_relative_error(self) -> float:
        v = float(
            self._get(
                C.ZORDER_QUANTILE_RELATIVE_ERROR,
                C.ZORDER_QUANTILE_RELATIVE_ERROR_DEFAULT,
            )
        )
        if not 0.0 < v < 1.0:
            raise HyperspaceError(f"{C.ZORDER_QUANTILE_RELATIVE_ERROR} must be in (0,1): {v}")
        return v

    # --- data skipping ---
    @property
    def dataskipping_target_index_data_file_size(self) -> int:
        return int(
            self._get(
                C.DATASKIPPING_TARGET_INDEX_DATA_FILE_SIZE,
                C.DATASKIPPING_TARGET_INDEX_DATA_FILE_SIZE_DEFAULT,
            )
        )

    @property
    def dataskipping_max_index_data_file_count(self) -> int:
        return int(
            self._get(
                C.DATASKIPPING_MAX_INDEX_DATA_FILE_COUNT,
                C.DATASKIPPING_MAX_INDEX_DATA_FILE_COUNT_DEFAULT,
            )
        )

    @property
    def dataskipping_auto_partition_sketch(self) -> bool:
        return self._as_bool(
            self._get(
                C.DATASKIPPING_AUTO_PARTITION_SKETCH,
                C.DATASKIPPING_AUTO_PARTITION_SKETCH_DEFAULT,
            )
        )

    # --- execution ---
    @property
    def exec_tpu_enabled(self) -> bool:
        return self._as_bool(
            self._get(C.EXEC_TPU_ENABLED, C.EXEC_TPU_ENABLED_DEFAULT)
        )

    @property
    def exec_exact_f64_aggregates(self) -> bool:
        return self._as_bool(
            self._get(C.EXEC_EXACT_F64_AGG, C.EXEC_EXACT_F64_AGG_DEFAULT)
        )

    @property
    def exec_mesh_devices(self) -> int:
        return int(self._get(C.EXEC_MESH_DEVICES, C.EXEC_MESH_DEVICES_DEFAULT))

    @property
    def exec_band_placement(self) -> str:
        v = str(self._get(C.EXEC_BAND_PLACEMENT, C.EXEC_BAND_PLACEMENT_DEFAULT))
        if v not in C.BAND_PLACEMENTS:
            raise HyperspaceError(
                f"{C.EXEC_BAND_PLACEMENT} must be one of {C.BAND_PLACEMENTS}, "
                f"got {v!r}"
            )
        return v

    @property
    def exec_mesh_slices(self) -> int:
        v = int(self._get(C.EXEC_MESH_SLICES, C.EXEC_MESH_SLICES_DEFAULT))
        if v < 1:
            raise HyperspaceError(f"{C.EXEC_MESH_SLICES} must be >= 1, got {v}")
        n = self.exec_mesh_devices
        if v > 1 and n % v:
            raise HyperspaceError(
                f"{C.EXEC_MESH_SLICES}={v} must divide "
                f"{C.EXEC_MESH_DEVICES}={n}"
            )
        return v

    @property
    def build_max_bytes_in_memory(self) -> int:
        return int(
            self._get(
                C.BUILD_MAX_BYTES_IN_MEMORY, C.BUILD_MAX_BYTES_IN_MEMORY_DEFAULT
            )
        )

    @property
    def index_format(self) -> str:
        v = str(self._get(C.INDEX_FORMAT, C.INDEX_FORMAT_DEFAULT)).lower()
        if v not in ("parquet", "arrow"):
            raise HyperspaceError(
                f"{C.INDEX_FORMAT} must be 'parquet' or 'arrow', got {v!r}"
            )
        return v

    @property
    def index_stats_columns(self) -> str:
        v = str(
            self._get(C.INDEX_STATS_COLUMNS, C.INDEX_STATS_COLUMNS_DEFAULT)
        ).lower()
        if v not in ("clustered", "all"):
            raise HyperspaceError(
                f"{C.INDEX_STATS_COLUMNS} must be 'clustered' or 'all', got {v!r}"
            )
        return v

    @property
    def index_compression(self) -> str:
        v = str(
            self._get(C.INDEX_COMPRESSION, C.INDEX_COMPRESSION_DEFAULT)
        ).lower()
        if v not in ("lz4", "none", "snappy", "zstd", "gzip"):
            raise HyperspaceError(
                f"{C.INDEX_COMPRESSION} must be one of lz4/none/snappy/zstd/gzip, "
                f"got {v!r}"
            )
        return v

    @property
    def event_logger_class(self) -> str | None:
        return self._conf.get(C.EVENT_LOGGER_CLASS)

    @property
    def display_mode(self) -> str:
        return str(self._get(C.DISPLAY_MODE, C.DISPLAY_MODE_DEFAULT))
