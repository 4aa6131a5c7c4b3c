"""Central flag namespace and defaults.

Reference parity: index/IndexConstants.scala:21-170 (spark.hyperspace.* keys).
Keys here drop the `spark.` prefix — this is not Spark — but keep the rest of
the dotted name so reference users recognize every knob.
"""

# --- toggles -----------------------------------------------------------------
APPLY_ENABLED = "hyperspace.apply.enabled"
APPLY_ENABLED_DEFAULT = True

# --- layout ------------------------------------------------------------------
SYSTEM_PATH = "hyperspace.system.path"  # default: <warehouse>/indexes (PathResolver)
INDEXES_DIR = "indexes"

# Transaction-log directory name under each index root
# (ref: index/IndexLogManager.scala:30 "_hyperspace_log").
HYPERSPACE_LOG = "_hyperspace_log"
LATEST_STABLE_LOG = "latestStable"

# Versioned index-data directory prefix (ref: index/IndexDataManager.scala:24-37).
INDEX_VERSION_DIR_PREFIX = "v__"

# --- covering index ----------------------------------------------------------
INDEX_NUM_BUCKETS = "hyperspace.index.numBuckets"
INDEX_NUM_BUCKETS_LEGACY = "hyperspace.num.buckets"  # legacy fallback key
INDEX_NUM_BUCKETS_DEFAULT = 8  # reference defaults to 200 (Spark shuffle default);
# on a TPU mesh one bucket per device-shard is the natural unit.

# Lineage column: stable source-file id recorded per index row
# (ref: index/IndexConstants.scala DATA_FILE_NAME_ID / lineage.enabled).
INDEX_LINEAGE_ENABLED = "hyperspace.index.lineage.enabled"
INDEX_LINEAGE_ENABLED_DEFAULT = False
DATA_FILE_NAME_ID = "_data_file_id"

# Nested (struct) source fields flatten to columns named
# "__hs_nested.<parent>.<leaf>" — the reference's ResolverUtils.ResolvedColumn
# normalization (util/ResolverUtils.scala), kept as the on-disk index column
# naming contract. User references by the bare dotted path ("a.b.c") resolve
# to the prefixed flat column.
NESTED_FIELD_PREFIX = "__hs_nested."

# --- hybrid scan -------------------------------------------------------------
HYBRID_SCAN_ENABLED = "hyperspace.index.hybridscan.enabled"
HYBRID_SCAN_ENABLED_DEFAULT = False
HYBRID_SCAN_MAX_APPENDED_RATIO = "hyperspace.index.hybridscan.maxAppendedRatio"
HYBRID_SCAN_MAX_APPENDED_RATIO_DEFAULT = 0.3
HYBRID_SCAN_MAX_DELETED_RATIO = "hyperspace.index.hybridscan.maxDeletedRatio"
HYBRID_SCAN_MAX_DELETED_RATIO_DEFAULT = 0.2

# --- rules -------------------------------------------------------------------
FILTER_RULE_USE_BUCKET_SPEC = "hyperspace.index.filterRule.useBucketSpec"
FILTER_RULE_USE_BUCKET_SPEC_DEFAULT = False

# --- optimize ----------------------------------------------------------------
OPTIMIZE_FILE_SIZE_THRESHOLD = "hyperspace.index.optimize.fileSizeThreshold"
OPTIMIZE_FILE_SIZE_THRESHOLD_DEFAULT = 256 * 1024 * 1024  # 256 MB
OPTIMIZE_MODE_QUICK = "quick"
OPTIMIZE_MODE_FULL = "full"
OPTIMIZE_MODES = (OPTIMIZE_MODE_QUICK, OPTIMIZE_MODE_FULL)

# --- refresh -----------------------------------------------------------------
REFRESH_MODE_INCREMENTAL = "incremental"
REFRESH_MODE_FULL = "full"
REFRESH_MODE_QUICK = "quick"
REFRESH_MODES = (REFRESH_MODE_INCREMENTAL, REFRESH_MODE_FULL, REFRESH_MODE_QUICK)

# --- caching -----------------------------------------------------------------
INDEX_CACHE_EXPIRY_SECONDS = "hyperspace.index.cache.expiryDurationInSeconds"
INDEX_CACHE_EXPIRY_SECONDS_DEFAULT = 300

# --- z-order covering --------------------------------------------------------
ZORDER_TARGET_SOURCE_BYTES_PER_PARTITION = (
    "hyperspace.index.zorder.targetSourceBytesPerPartition"
)
ZORDER_TARGET_SOURCE_BYTES_PER_PARTITION_DEFAULT = 1024 * 1024 * 1024  # 1 GB
ZORDER_QUANTILE_ENABLED = "hyperspace.index.zorder.quantile.enabled"
ZORDER_QUANTILE_ENABLED_DEFAULT = False
ZORDER_QUANTILE_RELATIVE_ERROR = "hyperspace.index.zorder.quantile.relativeError"
ZORDER_QUANTILE_RELATIVE_ERROR_DEFAULT = 0.01

# --- data skipping -----------------------------------------------------------
DATASKIPPING_TARGET_INDEX_DATA_FILE_SIZE = (
    "hyperspace.index.dataskipping.targetIndexDataFileSize"
)
DATASKIPPING_TARGET_INDEX_DATA_FILE_SIZE_DEFAULT = 256 * 1024 * 1024
DATASKIPPING_MAX_INDEX_DATA_FILE_COUNT = (
    "hyperspace.index.dataskipping.maxIndexDataFileCount"
)
DATASKIPPING_MAX_INDEX_DATA_FILE_COUNT_DEFAULT = 10000
DATASKIPPING_AUTO_PARTITION_SKETCH = (
    "hyperspace.index.dataskipping.autoPartitionSketch"
)
DATASKIPPING_AUTO_PARTITION_SKETCH_DEFAULT = True

# --- telemetry ---------------------------------------------------------------
EVENT_LOGGER_CLASS = "hyperspace.telemetry.eventLoggerClass"

# --- sources -----------------------------------------------------------------
FILE_BASED_SOURCE_BUILDERS = "hyperspace.index.sources.fileBasedBuilders"
# Conf-gated default-source format list (ref: HyperspaceConf.scala:110-115,
# DefaultFileBasedSource.scala:38-95 — same default set, same key shape)
DEFAULT_SOURCE_FORMATS = (
    "hyperspace.index.sources.defaultFileBasedSource.supportedFileFormats"
)
DEFAULT_SOURCE_FORMATS_DEFAULT = "avro,csv,json,orc,parquet,text"
GLOBBING_PATTERN_KEY = "hyperspace.source.globbingPattern"
# scan option carrying the original glob roots so relation reloads re-expand
OPT_GLOB_PATHS = "globPaths"

# --- explain -----------------------------------------------------------------
DISPLAY_MODE = "hyperspace.explain.displayMode"
DISPLAY_MODE_DEFAULT = "plaintext"
HIGHLIGHT_BEGIN_TAG = "hyperspace.explain.displayMode.highlight.beginTag"
HIGHLIGHT_END_TAG = "hyperspace.explain.displayMode.highlight.endTag"

# --- execution (TPU-native; no reference analogue) ---------------------------
# Devices to execute supported fragments over (0 = single-device). With a
# multi-chip mesh, fragment rows shard across devices and only per-group
# partial vectors cross the interconnect.
EXEC_MESH_DEVICES = "hyperspace.tpu.exec.meshDevices"
EXEC_MESH_DEVICES_DEFAULT = 0
# Multi-slice topology: arrange meshDevices as (meshSlices, devices/slice)
# with ("dcn", "ici") axes. Query-fragment aggregates then psum over the
# axis pair — XLA reduces within each slice over ICI and only per-group
# partials cross DCN. 1 = single slice (flat 1-D mesh). Index builds split
# source rows across the slices and exchange on each slice's own submesh,
# so the bucket all_to_all rides ICI only (one sorted run per slice per
# bucket, the streaming-build layout).
EXEC_MESH_SLICES = "hyperspace.tpu.exec.meshSlices"
EXEC_MESH_SLICES_DEFAULT = 1
# Where a served query's join bands run on a deployment of several chips:
# "home" runs every band on the query's home chip; "spread" has the
# skew-aware placer (parallel/placement.py) pack them over all the chips.
EXEC_BAND_PLACEMENT = "hyperspace.tpu.exec.bandPlacement"
EXEC_BAND_PLACEMENT_DEFAULT = "home"
BAND_PLACEMENTS = ("home", "spread")
# Fused-XLA execution of supported plan fragments. Off by default on CPU
# (host numpy path is exact float64); bench/production TPU sessions turn it on.
EXEC_TPU_ENABLED = "hyperspace.tpu.exec.enabled"
EXEC_TPU_ENABLED_DEFAULT = False

# f64 Sum/Avg inputs in the fused device join+aggregate: by default they
# ship as f32 and accumulate on device (per-element relative error <= 2^-24,
# group-sum error well under 1e-6 relative for the small per-key groups the
# fused shape produces — same accuracy class as the scan-side f32 Pallas
# reductions that have always shipped). Setting this true restores the
# strict round-3 behavior: f64 Sum/Avg inputs always take the exact-f64
# host twin, so device and host tiers agree bit-for-bit.
EXEC_EXACT_F64_AGG = "hyperspace.tpu.exec.exactF64Aggregates"
EXEC_EXACT_F64_AGG_DEFAULT = False

# Out-of-core builds: source batches larger than this stream through the
# bucketed writer in file groups (bounded memory; buckets get one sorted run
# per group, compacted later by Optimize).
BUILD_MAX_BYTES_IN_MEMORY = "hyperspace.tpu.build.maxBytesInMemory"
BUILD_MAX_BYTES_IN_MEMORY_DEFAULT = 2 * 1024 * 1024 * 1024  # 2 GB

# Index DATA file format: "parquet" (default; reference layout parity) or
# "arrow" (Arrow IPC: ~3x faster single-core writes, mmap reads). Readers
# dispatch on file extension, so indexes written under either setting stay
# readable regardless of the current conf.
INDEX_FORMAT = "hyperspace.tpu.index.format"
INDEX_FORMAT_DEFAULT = "parquet"

# Parquet row-group statistics scope for index data files: "clustered"
# (default) writes min/max only for the columns the layout actually sorts or
# z-orders by — the only ones whose statistics prune row groups — cutting
# encode time ~20% on numeric-heavy slices; "all" restores stats on every
# column (matches what Spark's parquet writer does for the reference).
INDEX_STATS_COLUMNS = "hyperspace.tpu.index.statsColumns"
INDEX_STATS_COLUMNS_DEFAULT = "clustered"

# Compression codec for index data files ("lz4" default; "none" trades ~2x
# disk for ~20% faster single-core encodes, "zstd"/"snappy"/"gzip" also accepted).
INDEX_COMPRESSION = "hyperspace.tpu.index.compression"
INDEX_COMPRESSION_DEFAULT = "lz4"

# Log-entry id numbering (ref: actions/Action.scala baseId+1 transient, +2 final).
LOG_ID_TRANSIENT_OFFSET = 1
LOG_ID_FINAL_OFFSET = 2
