"""Global constants of the TPU-native bitmap index.

These mirror the reference's layout constants so that on-disk data and query
semantics stay compatible (reference: fragment.go:50-61, roaring/roaring.go:32),
while the in-HBM representation is redesigned for TPU: a shard's row is a dense
little-endian bitvector of ``SHARD_WIDTH`` bits stored as uint32 lanes, the
natural operand shape for XLA bitwise ops and `lax.population_count`.
"""

# Number of columns in a shard. Row r of shard s covers absolute bit positions
# [r * SHARD_WIDTH, (r+1) * SHARD_WIDTH)  (reference: fragment.go:50-51,
# pos() fragment.go:2420-2424).
SHARD_WIDTH_EXP = 20
SHARD_WIDTH = 1 << SHARD_WIDTH_EXP  # 1,048,576 columns

# Dense on-device layout: uint32 lanes, little-endian bit order within a lane.
# Bit position p lives at word p >> 5, bit p & 31. This matches the roaring
# bitmap-container layout (1024 x uint64 little-endian words per 2^16-bit
# container, roaring/roaring.go:53) so host<->device conversion is a memcpy.
WORD_BITS = 32
WORDS_PER_SHARD = SHARD_WIDTH // WORD_BITS  # 32,768 uint32 lanes = 128 KiB

# Roaring container geometry (reference: roaring/roaring.go:53-62,1258-1261).
CONTAINER_BITS = 1 << 16
CONTAINERS_PER_SHARD = SHARD_WIDTH // CONTAINER_BITS  # 16
ARRAY_MAX_SIZE = 4096   # array container -> bitmap container threshold
RUN_MAX_SIZE = 2048     # max intervals in a run container

# Fragment write-ahead behavior (reference: fragment.go:76-79).
MAX_OP_N = 2000          # ops before snapshot compaction
HASH_BLOCK_SIZE = 100    # rows per anti-entropy checksum block

# Cluster geometry (reference: cluster.go:40-42).
DEFAULT_PARTITION_N = 256
DEFAULT_REPLICA_N = 1

# Cache defaults (reference: field.go:42-45).
DEFAULT_CACHE_SIZE = 50000

# Name of the per-index existence field (reference: pilosa.go existenceFieldName).
EXISTENCE_FIELD_NAME = "_exists"

# On-disk roaring format magic (reference: roaring/roaring.go:32).
MAGIC_NUMBER = 12348
STORAGE_VERSION = 0

# Kernel-family inventory: every family string passed to
# utils/telemetry.py counted_jit / record_dispatch must be registered
# here, with the device representation its latency histograms are
# attributed to. pilosa-lint's kernel-family rule (analysis/lint.py)
# checks call sites against this table, so a new kernel cannot ship
# unattributed in the pilosa_kernels* metric families. This lives in
# constants (import-free) so the linter never has to import jax.
KERNEL_FAMILY_REPS = {
    "topn": "dense",         # ops/topn.py cache ranking
    "bsi": "dense",          # ops/bsi.py bit-sliced planes
    "bitwise": "dense",      # ops/bitvector.py dense plane programs
    "count": "dense",        # ops/bitvector.py popcounts
    "groupby": "dense",      # ops/bitvector.py GroupBy folds
    "sparse": "sparse",      # ops/bitvector.py sorted-index kernels
    "run": "run",            # ops/bitvector.py interval-pair kernels
    "ingest": "dense",       # ops/bitvector.py bulk write patching
    "program": "dense",      # parallel/mesh.py fused bitmap programs
    "stream": "dense",       # parallel/mesh.py streaming folds
    "batcher": "dense",      # parallel/batcher.py batched dispatches
    "ici_program": "dense",  # parallel/mesh.py shard_map programs
    "stream_mesh": "dense",  # parallel/mesh.py sharded streaming
    "groupby_mesh": "dense",  # parallel/mesh.py sharded GroupBy
}
KERNEL_FAMILIES = frozenset(KERNEL_FAMILY_REPS)
