"""Typed run configuration.

One typed config object replacing the reference's three coexisting generations
(attrs RunConfig at fedml_core/trainer/model_trainer.py:7-38, click CLIs at
fedml_experiments/distributed/fedavg/main_fedavg.py:24-57, legacy argparse).
Frozen dataclasses so configs are hashable and safe to close over in jit.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

# Choice tuples mirroring fedml_experiments/base.py:18-46.
PARTITION_METHODS = ("hetero", "homo", "hetero-fix")
CLIENT_OPTIMIZERS = ("sgd", "adam")
SERVER_OPTIMIZERS = ("sgd", "momentum", "adam", "yogi", "adagrad")


@dataclasses.dataclass(frozen=True)
class DataConfig:
    """Dataset + partitioning (ref RunConfig.dataset fields)."""

    dataset: str = "synthetic"
    data_dir: str = "./data"
    partition_method: str = "hetero"  # LDA label-skew
    partition_alpha: float = 0.5
    batch_size: int = 32
    # Bucket padded per-client sample counts to multiples of this to bound the
    # number of distinct jit shapes (see data/base.py).
    pad_bucket: int = 1
    # Keep the whole dataset resident in device HBM and gather sampled
    # clients on-device each round (data/device_store.py) — avoids the
    # per-round host->device batch transfer. Auto-falls-back to host
    # stacking when the dataset exceeds the HBM budget guard.
    device_cache: bool = True


@dataclasses.dataclass(frozen=True)
class FedConfig:
    """Federation topology/round structure (ref RunConfig federation fields)."""

    client_num_in_total: int = 10
    client_num_per_round: int = 10
    comm_round: int = 10
    epochs: int = 1  # local epochs per round
    frequency_of_the_test: int = 1
    ci: bool = False  # CI short-circuit (ref FedAVGAggregator.py:119-126)
    # Hierarchical FL (ref standalone/hierarchical_fl/trainer.py:43-69):
    # clients → group_num groups; each global round runs group_comm_round
    # FedAvg sub-rounds inside every group before the cross-group average.
    group_num: int = 1
    group_comm_round: int = 1
    # Client selection policy (scheduler/policies.py registry): "uniform"
    # (reference-parity round-seeded draw), "weighted" (by local sample
    # counts), "power_of_choice" (loss-biased d-choose-k, Cho et al. 2020),
    # "straggler_aware" (avoids telemetry-flagged stragglers). All
    # round-keyed and seed-deterministic; uniform/weighted select
    # identical cohorts across the simulation and transport runtimes,
    # the adaptive two share the rule but feed on runtime-local signals
    # (docs/SCHEDULING.md).
    selection: str = "uniform"
    # Select ceil(client_num_per_round * factor) clients per round —
    # deadline/quorum rounds still close with ~k useful uploads when part
    # of the cohort drops. 1.0 = off. Transport runners spawn one worker
    # per overprovisioned slot.
    overprovision_factor: float = 1.0
    # Fault-injection plan (scheduler/faults.py): inline JSON or a path to
    # a JSON file ({seed, default, clients: {id: {dropout_p, slowdown_s,
    # crash_at_round, flaky_upload_p}}}); "" = no injected faults.
    # Deterministic per (plan seed, client, round), so CI can exercise the
    # deadline/quorum and staleness recovery paths on purpose.
    fault_plan: str = ""
    # Straggler tolerance for the transport runtime (the reference's
    # aggregator barrier waits forever — FedAVGAggregator.py:43-49, SURVEY §5
    # "no straggler mitigation"). deadline_s > 0: after broadcasting, the
    # server waits at most deadline_s for uploads; once the deadline passes
    # and at least min_clients have reported, it aggregates the partial set
    # and discards late round-tagged uploads. 0 = wait for all (ref parity).
    deadline_s: float = 0.0
    min_clients: int = 1
    # Round pipeline: while round r's programs execute on
    # device (JAX dispatch is async), the host prepares round r+1 —
    # cohort selection, batch gather/stack, H2D placement — and stashes
    # the placed batch for the round boundary (the _warm_placed commit
    # contract warmup already uses). Inputs are pure in
    # (round, config.seed, rng), so numerics are BYTE-IDENTICAL to the
    # serial schedule (tests/test_pipeline.py). "auto" (default)
    # pipelines wherever that purity holds and degrades to serial
    # automatically: adaptive selection (power_of_choice /
    # straggler_aware need round r's signals before selecting r+1) and
    # active fault plans that shrink cohorts. "on" is an alias
    # of "auto" (the degradations are correctness rules, not
    # preferences); "off" forces the serial schedule. Overlap is
    # measured and folded per round as flight `overlap_s`.
    pipeline: str = "auto"
    # Eval rounds evaluate on every client's local train/test shards
    # (ref _local_test_on_all_clients, fedavg_api.py:117-180) instead of the
    # central test set.
    eval_on_clients: bool = False
    # Asynchronous buffered aggregation knobs, consumed by the FedBuff
    # runtime (algorithms/fedbuff.py, selected via the fedbuff entry
    # points / CLI --algorithm fedbuff — beyond the reference, whose
    # aggregator barrier waits for every worker forever,
    # FedAVGAggregator.py:43-49). Under FedBuff the server never barriers:
    # every upload is answered immediately with the current model, and the
    # global model advances whenever the buffer holds async_buffer_k client
    # deltas, each discounted by staleness (1+tau)^(-async_staleness_exp)
    # and scaled by async_server_lr. comm_round then counts SERVER STEPS
    # (buffer flushes), not synchronous rounds. The synchronous runtimes
    # ignore these fields.
    async_buffer_k: int = 0
    async_staleness_exp: float = 0.5
    async_server_lr: float = 1.0
    # How the round executes the sampled clients' local trainings on one
    # chip: "vmap" batches them (one program, grouped convs/batched matmuls
    # — best for small models where per-step overhead dominates), "scan"
    # runs them sequentially (each client's convs keep full MXU tiling —
    # measured 1.8x faster for conv models whose channel dims are small
    # relative to the 128-lane MXU, e.g. the cross-silo ResNet-56 round:
    # 339 ms -> 190 ms bf16 on v5e).
    # "auto" picks scan for conv models with a client param copy >= 1 MB.
    client_parallelism: str = "auto"
    # Where stateful algorithms (SCAFFOLD control variates, Ditto personal
    # models) keep their N × |params| per-client state: "device" pins the
    # stacked pytree in HBM (gather/scatter inside the jitted round),
    # "mmap" spills it to a disk-backed store (cohort rows ride to device
    # per round — the same disk→host→HBM tiering as data/mmap_store.py),
    # "sharded" spills to the record-major fixed-stride tier
    # (population/state_tier.py — one contiguous record per client,
    # sharded files; the million-client form), "auto" picks device while
    # the stack fits state_budget_bytes and spills beyond it (sharded
    # at/above PopulationConfig.ocohort_threshold clients, mmap below).
    # Round 3 REFUSED past the budget; now it
    # spills instead.
    state_store: str = "auto"
    state_budget_bytes: int = 8 << 30
    state_dir: str = ""  # "" = a fresh temp dir per run


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    """Local (client) optimizer settings (ref MyModelTrainer.get_optimizer)."""

    client_optimizer: str = "sgd"
    lr: float = 0.03
    wd: float = 0.0
    momentum: float = 0.0
    # FedProx proximal term; 0 = plain FedAvg. The reference's distributed
    # fedprox omits mu entirely (SURVEY §2b) — fixed here.
    prox_mu: float = 0.0
    # Mixed-precision policy: params + optimizer state stay float32 (master
    # weights); forward/backward run in this dtype. "bfloat16" is the TPU
    # MXU-native dtype (the reference is fp32-only torch).
    compute_dtype: str = "float32"
    # Device-side augmentation policy applied inside the jitted train step
    # (train/augment.py): "none" | "cifar" (crop pad-4 + flip + Cutout 16,
    # the reference's CifarDataLoader transforms, base.py:136-146) |
    # "crop_flip".
    augment: str = "none"


@dataclasses.dataclass(frozen=True)
class ServerConfig:
    """Server-side optimizer for the FedOpt family
    (ref fedml_api/distributed/fedopt/FedOptAggregator.py:95-117)."""

    server_optimizer: str = "sgd"
    server_lr: float = 1.0
    server_momentum: float = 0.0
    tau: float = 1e-3  # adaptivity for yogi/adam


@dataclasses.dataclass(frozen=True)
class CommConfig:
    """Cross-silo transport options (core/). The reference ships raw
    JSON-list tensors with no compression option anywhere; here the binary
    wire can additionally carry compressed client UPLINK updates
    (core/compression.py): the client sends encode(w_local − w_round) and
    the server reconstructs w_round + decode(...) before aggregating.
    Downlink (broadcast) is exact by default; ``downlink_compression``
    optionally ships the round's model itself int8-quantized — encoded
    ONCE per round through the same codec registry, with both ends
    training/decoding against the identical dequantized tree."""

    # "none" | "int8" (per-tensor linear quantization) | "int4" (packed
    # low-bit: 4-bit levels, two per byte — ~8x; pair with
    # error_feedback) | "topk" (magnitude sparsification at topk_frac
    # density) | "topk8" (top-k with int8-quantized values).
    compression: str = "none"
    topk_frac: float = 0.01
    # Downlink (broadcast) quantization, transport runtimes: "none"
    # ships the fp32 model; "int8" encodes it once per round
    # (core/compression.py encode_int8 — per-tensor symmetric scales)
    # and every worker's envelope carries the SAME payload. The server
    # keeps the dequantized tree as the round's reference — clients
    # train from it and uplink deltas encode/decode against it on both
    # ends, so quantized downlink composes with every uplink codec.
    # Payload-vs-raw bytes are metered per broadcast (comm/downlink_*).
    downlink_compression: str = "none"
    # Lossy codecs (topk/topk8/int4/int8): per-client residual memory
    # (error feedback) — dropped coordinates AND quantization error
    # accumulate and ship in later rounds instead of being lost. Off by
    # default (stateless-client parity with the reference).
    error_feedback: bool = False
    # Transport send retry (core/retry.py, applied once in the
    # BaseCommManager send template): a failed send is retried up to this
    # many times under seed-deterministic jittered exponential backoff.
    # 0 = legacy single-attempt sends. At-least-once safe: FedBuff
    # dedupes restated uploads on the dispatch tag, the sync server on
    # (client, round)/worker slot.
    send_retries: int = 0
    send_backoff_s: float = 0.05  # backoff base (doubles per retry)
    send_backoff_max_s: float = 2.0  # per-sleep cap
    # Total wall-clock a logical send may spend across attempts + backoff
    # sleeps; the send gives up early when the next sleep would cross it.
    # 0 = attempts cap only.
    send_retry_deadline_s: float = 0.0
    # Per-RPC deadline for grpc sends (was a hard-coded 30.0 in
    # grpc_comm._send). With send_retries > 0 the retry layer owns
    # reconnects: every attempt — including first contact, which still
    # waits for the peer's server to bind — is capped here instead of
    # the legacy one-shot 120 s wait_for_ready handshake.
    send_timeout_s: float = 30.0
    # Transport chaos: probability an individual send ATTEMPT fails with
    # an injected transient error before reaching the wire — pure in
    # (seed, send seq, attempt), so a flaky-transport run replays
    # identically. The eventual successful attempt delivers exactly once
    # (numerics identical to a fault-free run). Requires send_retries > 0.
    send_fault_p: float = 0.0
    # Boundary-wire quantization for the split/vertical runtimes
    # (fedml_tpu/splitfed/codec.py): per-batch activations, activation
    # grads, and VFL logit contributions ship int8/int4-quantized through
    # the same codec registry the model path uses (topk variants are
    # delta-sparsity codecs — activations are dense, so they're
    # rejected). "none" ships fp32 tensors. Metered per boundary message
    # (comm/uplink_* for acts/contribs, comm/downlink_* for grads), so
    # the cut factor is read off comm/*, never asserted.
    activation_compression: str = "none"
    # Per-stream residual memory over the boundary tensors: each
    # direction of each (peer, shape) stream folds its quantization
    # error into the next same-shape tensor before encoding (the split
    # analogue of error_feedback's per-client residual).
    activation_error_feedback: bool = False
    # Secure aggregation in the round loop (ref distributed turboaggregate):
    # clients upload pairwise-masked field vectors of their weighted
    # deltas; the server only ever sums masked uploads, and a quorum round
    # (deadline_s) triggers dropout mask recovery. Protocol SIMULATION —
    # the DH registry is derived deterministically from the run seed (see
    # secagg/secure_aggregation.py SECURITY NOTE); mutually exclusive with
    # compression.
    secure_agg: bool = False
    # gRPC server executor size (core/grpc_comm.py — was a hard-coded
    # ThreadPoolExecutor(max_workers=8)). 0 = auto: sized from the
    # expected cohort (the rank's ip_config table), capped — handler
    # work is a queue put, so a small pool serves thousands of streams;
    # the bound is what the fleet gate ASSERTS (examples/ci.sh).
    grpc_max_workers: int = 0
    # Inbound stream budget (server-side backpressure): when > 0, a
    # received RPC is REFUSED (RESOURCE_EXHAUSTED) while more than this
    # many messages sit undrained in the receive queue — graceful
    # refusal instead of unbounded queue growth; the refused sender
    # redials under its retry policy (core/retry.py) and both ends
    # meter the refusal (comm/refused, comm/send_refused). 0 = off.
    grpc_stream_budget: int = 0
    # gRPC channel/server max message size in MB (was the module-constant
    # 1000 MB mirroring the reference's grpc_comm_manager.py:35-39).
    grpc_max_message_mb: int = 1000
    # gRPC keepalive ping interval in seconds; 0 = transport default
    # (no explicit keepalive options). Long-lived fleet channels set
    # this so half-open connections die instead of wedging a worker.
    grpc_keepalive_s: float = 0.0
    # MiniMqttBroker connection cap (core/mqtt_broker.py): past it a
    # CONNECT is answered CONNACK 0x03 (server unavailable) and closed
    # instead of growing one reader thread per connection without
    # bound; refusals are metered (comm/refused). 0 = unbounded
    # (legacy behavior).
    mqtt_max_connections: int = 0
    # Client telemetry beacons (telemetry/wire.py): a bounded ~200 B
    # summary of local measurements (train s, encode s, retries, codec,
    # DeviceProfile tier, RSS) piggybacked as ARG_TELEMETRY on model
    # uploads. Observability only — it rides the envelope, never the
    # model path, so numerics are byte-identical on or off; bytes are
    # metered apart from model bytes (comm/beacon_bytes).
    beacons: bool = True


@dataclasses.dataclass(frozen=True)
class CompileConfig:
    """Compile-runtime knobs (fedml_tpu/compile/ — the reference framework
    is PyTorch eager and has no compilation cost dimension at all)."""

    # AOT-compile the run's programs before round 0
    # (``jit(...).lower(...).compile()``, compile/warmup.py): round + eval
    # + server-optimizer programs on vmap/mesh, the shared client
    # local-train program on the sync transports (so --deadline_s rounds
    # start with compilation already paid). Numerics are identical to a
    # cold run — warmup only lowers/compiles, it executes nothing.
    warmup: bool = False
    # Persistent XLA compile-cache directory served by the hardened store
    # (compile/persistent.py: atomic writes, sha256 integrity check with
    # quarantine, advisory file lock). "" = <checkout>/.jax_cache; where
    # JAX_COMPILATION_CACHE_DIR is set it wins over either
    # (compile/persistent.resolve_cache_dir).
    cache_dir: str = ""
    # Only persist compiles at least this slow. The conservative 2 s
    # default matches tests/conftest.py: aggressive thresholds (0.3-0.5 s)
    # corrupted the heap on this jaxlib under the STOCK cache (ROADMAP
    # "compile-cache hygiene"); the hardened store tolerates 0 (the
    # zero-cold-start CI stage runs it), but the default stays safe.
    min_compile_time_s: float = 2.0
    # Persistent SERIALIZED-EXECUTABLE store (compile/executable_cache.py)
    # served through the hardened store: --warmup exports every AOT
    # executable it compiles, keyed by (program digest, shape class,
    # environment fingerprint), and a fresh process deserializes its
    # whole warmup set instead of compiling it — zero-cold-start serving.
    # Version/backend/code skew lands on a different key (clean miss,
    # recompile), never wrong numerics. "" = off.
    executable_cache: str = ""
    # Recompile budget (fedml_tpu/analysis/sentinel.py): fail the run when
    # more than this many XLA backend compiles happen — the tripwire for
    # cache-key instabilities that silently recompile every round. Counts
    # every ACTUAL backend compile (including small utility programs —
    # but NOT persistent-cache hits or deserialized executables, which
    # compile nothing: a fully warm process passes budget 0, the
    # zero-cold-start CI gate). Budgets are coarse upper bounds asserting
    # "no compile storm", not exact program counts. None = unlimited (no
    # sentinel).
    recompile_budget: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class PopulationConfig:
    """Population-scale runtime knobs (fedml_tpu/population/ — the
    O(cohort) machinery for 1M+ client registries, docs/POPULATION.md).

    Every field here steers HOST-SIDE data structures (samplers, mmap
    index/state layout, telemetry bounds); none can reach a compiled
    program, so the whole section is classified KNOWN_BENIGN in the
    digest audit (analysis/digest_audit.py)."""

    # Client count at/above which the O(cohort) selection paths engage
    # (alias-table weighted draw, rejection-sampled candidate pools,
    # rejection-sampled straggler avoidance). Below it the legacy exact
    # numpy draws run — identical cohorts to every historical run.
    ocohort_threshold: int = 65536
    # PopulationIndex (population/index.py): back the packed per-client
    # metadata arrays with an on-disk memmap once they exceed this many
    # bytes (0 = always in RAM). Only matters when index_dir is set.
    index_mmap_bytes: int = 64 << 20
    index_dir: str = ""  # "" = keep the packed index in RAM
    # Sharded state tier (population/state_tier.py): clients per shard
    # file = 1 << state_shard_bits (default 65536/shard — 1M clients
    # land in 16 record files).
    state_shard_bits: int = 16
    # power_of_choice bias map bound (scheduler/policies.py): the
    # scheduler keeps at most this many last-known client losses
    # (insertion-ordered eviction). Bounds the "sched" checkpoint slot —
    # an unbounded map grows O(N) at million-client populations.
    loss_map_capacity: int = 65536
    # How many most-recent rounds of the selection memo the scheduler
    # checkpoint persists (resume only ever re-selects the in-flight
    # round; the full memo would grow O(rounds) in the checkpoint).
    selection_memo_rounds: int = 64
    # Health registry bounds (telemetry/health.py): full-fidelity
    # (timing window + dedupe memory) client records are an LRU active
    # set of at most this many recently-seen clients; evicted records
    # spill to a compact aggregate (~100 B/client).
    health_active_clients: int = 65536
    # Registry-wide byte budget for the full-fidelity fault-event log
    # backing FaultTrace export. Past it the registry keeps exact fault
    # TALLIES but stops recording events and marks affected clients
    # trace_incomplete (FaultPlan.from_trace refuses them — a partial
    # fleet must never replay silently).
    health_trace_budget_bytes: int = 16 << 20
    # Round flight recorder bounds (telemetry/flight.py): the per-round
    # ring keeps at most flight_rounds folded records AND never more
    # than flight_budget_bytes of them (whichever bound is tighter wins
    # — a month-long serve tenant stays O(K), never O(rounds), exactly
    # like the fault-event log above).
    flight_rounds: int = 64
    flight_budget_bytes: int = 64 << 10


@dataclasses.dataclass(frozen=True)
class AdminConfig:
    """Control-plane knobs a tenant carries to the serve layer
    (fedml_tpu/serve/: placement.py, admission.py, admin.py —
    docs/SERVING.md). Single runs ignore them.

    Every field is HOST-SIDE service policy — which slice a tenant is
    scheduled on and what the admission door requires — and none can
    reach a compiled program, so the section is classified KNOWN_BENIGN
    in the digest audit (analysis/digest_audit.py), exactly like
    PopulationConfig."""

    # Placement pin: run this tenant on slice index N of the service's
    # device slices (serve --device_slices). -1 = let the placer bin-pack
    # onto the least-loaded slice. Pinning two same-model-family tenants
    # to ONE slice preserves their cross-tenant executable sharing (XLA
    # compiles per device — crossing slices costs one compile).
    device_slice: int = -1
    # Admission: refuse this tenant when host MemAvailable is below this
    # many MB at the door (0 = no headroom requirement).
    admit_min_headroom_mb: float = 0.0
    # Admission: refuse when the tenant's priced compute — measured
    # per-dispatch XLA cost-analysis flops x cohort size — exceeds this
    # many GFLOP per round (0 = no cap; unpriced candidates pass).
    admit_cost_cap_gflops: float = 0.0


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Device-mesh spec replacing the reference's gpu_mapping.yaml
    (fedml_api/distributed/utils/gpu_mapping.py:8-39)."""

    # Number of mesh shards along the client axis; None = all local devices.
    client_shards: Optional[int] = None
    axis_name: str = "clients"


@dataclasses.dataclass(frozen=True)
class RunConfig:
    """Top-level config threaded through every API (ref RunConfig)."""

    data: DataConfig = dataclasses.field(default_factory=DataConfig)
    fed: FedConfig = dataclasses.field(default_factory=FedConfig)
    train: TrainConfig = dataclasses.field(default_factory=TrainConfig)
    server: ServerConfig = dataclasses.field(default_factory=ServerConfig)
    comm: CommConfig = dataclasses.field(default_factory=CommConfig)
    mesh: MeshConfig = dataclasses.field(default_factory=MeshConfig)
    compile: CompileConfig = dataclasses.field(default_factory=CompileConfig)
    population: PopulationConfig = dataclasses.field(
        default_factory=PopulationConfig
    )
    admin: AdminConfig = dataclasses.field(default_factory=AdminConfig)
    model: str = "lr"
    seed: int = 0

    def replace(self, **kw) -> "RunConfig":
        return dataclasses.replace(self, **kw)
