"""TPU-native ETL subsystem — the DataVec replacement.

The survey's scope fact: DataVec is an *external* dependency of the
reference repo, so this rebuild ships its own ETL layer. Four cooperating
pieces, one import surface:

- `schema` / `transform` — declarative column `Schema` over record streams
  and a chainable, JSON-serializable `TransformProcess` (categorical ->
  one-hot/integer, min-max & z-score normalize, row filters,
  derived/renamed/removed columns, sequence windowing), executed
  *vectorized* on NumPy column batches.
- `normalizer` — `DataNormalizer` (`NormalizerStandardize` via streaming
  Welford, `NormalizerMinMaxScaler`): `fit(iterator)` one pass,
  `transform`/`revert` on DataSets, stats persisted through ModelSerializer
  (`normalizer.json` in the model zip) so serving applies the identical
  preprocessing.
- `pipeline` — `ParallelPipelineExecutor`: N-worker read -> transform ->
  batch pipeline over MagicQueue with ordered or unordered delivery,
  backpressure, deterministic close()/drain, and exactly-once error
  propagation to the consumer.
- `prefetch` — `DevicePrefetcher`: double/triple-buffered `jax.device_put`
  ahead of the consuming step, with a sharded mode that splits each batch
  across the mesh (parallel/sharding) so `network.fit` and ParallelWrapper
  receive already-resident, already-sharded arrays — plus the narrow-wire
  ingest mode (`transfer_dtype`/`device_transform`/`transfer_streams`).
- `device_transform` — `DeviceIngest` / `lower_normalizer`: compile a fitted
  TransformProcess + DataNormalizer into traceable jnp `apply_features` /
  `apply_labels`, so the host ships raw uint8/int records and the first
  fused ops of the jitted step do decode/cast/normalize/one-hot ON CHIP
  (`network.set_ingest`; serving reuses the same lowering per version).

Everything is instrumented through the telemetry layer: per-stage spans,
`etl_batches_total` / `etl_records_total`, `etl_queue_depth`, and the
prefetcher's legs as phases on the profiler's clock (`etl_h2d`,
`etl_device_transform`, `etl_producer_blocked`, `etl_consumer_wait`; each a
`<leg>_ms{pipeline}` histogram). `etl_consumer_wait_ms` is the
device-starvation signal for a consumer that waits for its step; under
`fit(steps_per_execution=K)`, which keeps one execution queued behind the
running one, `fit_executions_ahead_total{ahead="0"}` is the signal
(prefetch.py).
"""
from .device_transform import DeviceIngest, lower_normalizer
from .normalizer import (DataNormalizer, NormalizerMinMaxScaler,
                         NormalizerStandardize)
from .pipeline import ParallelPipelineExecutor
from .prefetch import DevicePrefetcher
from .schema import Column, ColumnType, Schema
from .transform import TransformProcess

__all__ = ["Schema", "Column", "ColumnType", "TransformProcess",
           "DataNormalizer", "NormalizerStandardize",
           "NormalizerMinMaxScaler", "ParallelPipelineExecutor",
           "DevicePrefetcher", "DeviceIngest", "lower_normalizer"]
