"""disq_tpu — a TPU-native framework for reading and writing
high-throughput-sequencing formats (BAM / CRAM / SAM / VCF) as sharded
columnar arrays over a `jax.sharding.Mesh`.

Capability parity target: `tomwhite/disq` (a JVM/Spark library; see
SURVEY.md). Where disq decomposes files into Spark RDD partitions and
delegates byte-level codec work to htsjdk, disq_tpu decomposes files into
device shards and owns the codecs natively:

- host layer (``disq_tpu.fsw``) stages byte ranges (posix/GCS) —
  the analogue of disq's ``FileSystemWrapper`` / ``PathSplitSource``
  (reference: ``impl/file/FileSystemWrapper.java``, ``PathSplitSource.java``).
- ``disq_tpu.bgzf`` finds and codes BGZF blocks — the analogue of
  ``impl/formats/bgzf/BgzfBlockGuesser.java`` + htsjdk's
  ``BlockCompressedInputStream``/``OutputStream``.
- ``disq_tpu.bam`` decodes records into **columnar arrays** (pos, flag,
  cigar, 4-bit seq, qual, name/tag blobs) instead of per-record objects —
  replacing htsjdk's ``BAMRecordCodec`` + ``SAMRecord``.
- ``disq_tpu.sort`` coordinate-sorts across chips with a bucket/radix
  exchange over ICI collectives — replacing the caller-side Spark
  ``sortBy`` shuffle.
- ``disq_tpu.api`` mirrors disq's public L6 surface
  (``HtsjdkReadsRddStorage`` et al., ``HtsjdkReadsRddStorage.java``).
"""

__version__ = "0.1.0"

from disq_tpu.api import (  # noqa: F401
    ReadsStorage,
    FleetHandle,
    ServeHandle,
    VariantsStorage,
    ReadsDataset,
    VariantsDataset,
    TraversalParameters,
    WriteOption,
    ReadsFormatWriteOption,
    VariantsFormatWriteOption,
    FileCardinalityWriteOption,
    TempPartsDirectoryWriteOption,
    BaiWriteOption,
    SbiWriteOption,
    CraiWriteOption,
    TabixIndexWriteOption,
    StageManifestWriteOption,
    serve,
    serve_fleet,
)
from disq_tpu.runtime import (  # noqa: F401
    BreakerOpenError,
    ClusterAggregator,
    ColumnarBatch,
    CoordinatorLostError,
    CorruptBlockError,
    DeadlineExceededError,
    DisqOptions,
    ErrorPolicy,
    PipelineCounters,
    QuarantineManifest,
    ReadLedger,
    ShardCounters,
    StageManifest,
    WatchdogStallError,
    device_span,
    introspect_address,
    metrics_text,
    process_count,
    process_id,
    start_introspect_server,
    stop_introspect_server,
    phase_report,
    reduce_counters,
    span,
    start_span_log,
    stop_span_log,
    synced_timer,
    telemetry_snapshot,
    trace_phase,
)
