"""Unified CLI — the L5 experiment-driver layer (ref:
fedml_experiments/distributed/fedavg/main_fedavg.py:24-131 click flags +
fed_launch/main.py unified launcher + the 19 main_*.py drivers).

One command covers what the reference spreads over 19 drivers: flag surface
mirrors main_fedavg.py:24-57 (model/dataset/partition/optimizer/round flags),
`--algorithm` replaces the per-algorithm driver files — every algorithm
package is reachable here (the reference's L5 promise) — and `--runtime`
replaces `--backend MPI|GRPC|MQTT|TRPC` with the TPU-native choices:
``vmap`` (single-chip simulator, ref standalone/*), ``mesh`` (sharded
multi-chip SPMD, ref distributed/* over MPI), ``loopback`` (threaded
actor federation, transport parity path). GPU-mapping YAML flags become
`--client_shards` (mesh spec, SURVEY §5 config point). New in round 2:
``--resume`` (round-level checkpoint restore — the upgrade over the
reference's per-algorithm best-model saves, SURVEY §5), ``--compute_dtype
bfloat16`` (MXU-native mixed precision), ``--profile_dir`` (jax.profiler
trace capture)."""

from __future__ import annotations

import json
from pathlib import Path

import click
import numpy as np

from fedml_tpu.config import (
    AdminConfig,
    CommConfig,
    CompileConfig,
    DataConfig,
    FedConfig,
    MeshConfig,
    RunConfig,
    ServerConfig,
    TrainConfig,
)
from fedml_tpu.robustness import BYZANTINE_AGGREGATORS, CLIP_DEFENSES

ALGORITHMS = (
    "centralized",
    "fedavg",
    "fedopt",
    "fedprox",
    "fednova",
    "scaffold",  # beyond the reference: control-variate drift correction
    "fedbuff",  # beyond the reference: barrier-free async aggregation
    "ditto",  # beyond the reference: personalized FL (per-client models)
    "dp_fedavg",  # beyond the reference: client-level DP with RDP ledger
    "qfedavg",  # beyond the reference: q-FFL fair aggregation
    "hierarchical",
    "fedavg_robust",
    "fedgkt",
    "fedgan",
    "fedseg",
    "fednas",
    "split_nn",
    "vertical_fl",
    "decentralized",
    "secagg",
)
RUNTIMES = ("vmap", "mesh", "loopback", "mqtt", "shm", "grpc")


@click.command()
@click.option("--model", default="lr",
              help="Model name (models/registry.py); fedgkt/fednas/split_nn/"
                   "vertical_fl/decentralized/secagg use their own fixed "
                   "architectures and ignore this flag")
@click.option("--dataset", "dataset_name", default="synthetic", help="Dataset name (data/registry.py)")
@click.option("--data_dir", type=click.Path(path_type=Path), default=Path("./data"))
@click.option("--partition_method", type=click.Choice(("hetero", "homo", "hetero-fix")), default="hetero")
@click.option("--partition_alpha", type=float, default=0.5)
@click.option("--client_num_in_total", type=int, default=10)
@click.option("--client_num_per_round", type=int, default=10)
@click.option("--batch_size", type=int, default=32, help="-1 = full batch")
@click.option("--pad_bucket", type=int, default=1,
              help="round per-client step counts up to multiples of this "
                   "(shape-class bucketing: fewer XLA compiles on ragged "
                   "shards at the cost of a little padded compute)")
@click.option("--client_optimizer", type=click.Choice(("sgd", "adam")), default="sgd")
@click.option("--lr", type=float, default=0.03)
@click.option("--wd", type=float, default=0.0)
@click.option("--momentum", type=float, default=0.0)
@click.option("--epochs", type=int, default=1)
@click.option("--comm_round", type=int, default=10)
@click.option("--frequency_of_the_test", type=int, default=1)
@click.option("--eval_on_clients", is_flag=True, default=False,
              help="Eval on every client's local shards "
                   "(ref _local_test_on_all_clients) instead of the central test set")
@click.option("--algorithm", type=click.Choice(ALGORITHMS), default="fedavg")
@click.option("--runtime", type=click.Choice(RUNTIMES), default="vmap")
@click.option("--client_shards", type=int, default=None, help="Mesh shards (runtime=mesh); default all devices")
@click.option("--server_optimizer", default="sgd", help="FedOpt server optimizer")
@click.option("--server_lr", type=float, default=1.0)
@click.option("--server_momentum", type=float, default=0.0)
@click.option("--prox_mu", type=float, default=0.01, help="FedProx proximal term (algorithm=fedprox)")
@click.option("--defense", type=click.Choice(CLIP_DEFENSES + BYZANTINE_AGGREGATORS),
              default="norm_diff_clipping",
              help="fedavg_robust: clip/noise (ref) or Byzantine aggregator")
@click.option("--norm_bound", type=float, default=5.0,
              help="norm_diff_clipping/weak_dp: clip ||w_i - w_g|| to this")
@click.option("--noise_stddev", type=float, default=0.025,
              help="weak_dp: Gaussian noise stddev after averaging")
@click.option("--num_byzantine", type=int, default=1,
              help="assumed Byzantine client count (trimmed_mean trim-k, krum f)")
@click.option("--multi_krum_m", type=int, default=3,
              help="multi_krum: average the m best-scored clients")
@click.option("--attack", type=click.Choice(("none", "backdoor")), default="none",
              help="fedavg_robust: simulate attackers (poisoned shards + "
                   "boosted uploads, ref edge_case_examples) and report "
                   "Backdoor/ASR")
@click.option("--num_attackers", type=int, default=1,
              help="attack=backdoor: clients 0..k-1 are attackers")
@click.option("--attack_boost", type=float, default=10.0,
              help="model-replacement boost γ on attacker uploads")
@click.option("--poison_frac", type=float, default=0.5,
              help="fraction of each attacker shard triggered+relabeled")
@click.option("--target_label", type=int, default=0,
              help="backdoor target class")
@click.option("--group_num", type=int, default=2, help="hierarchical: number of groups")
@click.option("--group_comm_round", type=int, default=1)
@click.option("--compute_dtype", type=click.Choice(("float32", "bfloat16")), default="float32",
              help="Forward/backward dtype; params stay fp32 (master weights)")
@click.option("--augment", type=click.Choice(("none", "cifar", "crop_flip")), default="none",
              help="Device-side augmentation inside the jitted train step")
@click.option("--variant", default=None,
              help="Algorithm sub-variant: decentralized dsgd|pushsum, fednas arch_grad first|second")
@click.option("--seed", type=int, default=0)
@click.option("--log_dir", type=click.Path(path_type=Path), default=None)
@click.option("--checkpoint_path", type=click.Path(path_type=Path), default=None,
              help="Save (params, round) here on every test round and at the end")
@click.option("--resume", is_flag=True, default=False,
              help="Restore from --checkpoint_path and continue from the saved round")
@click.option("--profile_dir", type=click.Path(path_type=Path), default=None,
              help="Capture a jax.profiler trace of the run into this dir: "
                   "the device ops (each op_name names its program — "
                   "jit_round_fn, jit_device_store_gather, jit_eval_fn — and "
                   "its scope: local_train/forward_backward|optimizer_update|"
                   "keep_gate, aggregate, round_metrics, gather, mask_pad, "
                   "eval) and, on the host plane and the same clock, every "
                   "telemetry span as a fedml.<name> annotation")
@click.option("--telemetry_dir", type=click.Path(path_type=Path), default=None,
              help="Write host-side telemetry here: trace.json (Chrome "
                   "trace events on the tracer's own clock — round/"
                   "broadcast/local_train/aggregate/eval and, from the "
                   "simulator's loop, pack/prepare/select/stack/place/health/"
                   "flush/flush_wait spans; docs/OBSERVABILITY.md has the "
                   "table), health.json (per-client "
                   "participation/train-time/straggler registry) and "
                   "flight.json (the last-K-rounds flight-recorder ring: "
                   "per-round phase wall times + rolling p50/p95)")
@click.option("--prom_port", type=int, default=None,
              help="Serve Prometheus text exposition on "
                   "http://127.0.0.1:PORT/metrics for the duration of the "
                   "run (comm byte/message counters, latency histograms, "
                   "client health gauges); 0 picks an ephemeral port "
                   "(printed to stderr). Off by default.")
@click.option("--no_device_cache", is_flag=True, default=False,
              help="Disable the HBM-resident data store (data/device_store.py)")
@click.option("--pipeline", type=click.Choice(("off", "auto", "on")),
              default="auto",
              help="Round pipelining (sim runtimes): while round r runs on "
                   "device, prepare round r+1's cohort/batch/placement on "
                   "the host (algorithms/fedavg.py _pipeline_prepare). "
                   "Numerics are byte-identical to serial; adaptive "
                   "selection policies and active fault plans degrade to "
                   "serial automatically. 'on' is an explicit alias of "
                   "'auto'")
@click.option("--client_parallelism", type=click.Choice(("auto", "vmap", "scan")),
              default="auto",
              help="How one chip runs the sampled clients: vmap (batched) "
                   "or scan (sequential — faster for conv models whose "
                   "small channels under-tile the MXU); auto picks per model")
@click.option("--state_store",
              type=click.Choice(("auto", "device", "mmap", "sharded")),
              default="auto",
              help="Where scaffold/ditto keep their per-client state: HBM "
                   "stack (device), disk spill with cohort-only HBM rows "
                   "(mmap: one memmap per pytree leaf; sharded: record-"
                   "major fixed-stride shards for million-client "
                   "populations — population/state_tier.py), or auto by "
                   "size vs --state_budget_bytes and population scale")
@click.option("--state_budget_bytes", type=int, default=8 << 30,
              help="state_store=auto: spill the per-client state to disk "
                   "past this many bytes (default 8 GiB)")
@click.option("--state_dir", type=str, default="",
              help="Directory for the spilled state store (default: a "
                   "fresh temp dir per run)")
@click.option("--straggle_ms", type=float, default=0.0,
              help="Simulated compute heterogeneity for THIS rank's "
                   "clients: sleep this long after every local training "
                   "(drives the straggler/async benchmarks)")
@click.option("--qffl_q", type=float, default=1.0,
              help="algorithm=qfedavg: fairness exponent q (0 = plain "
                   "FedAvg; larger = more uniform accuracy across clients)")
@click.option("--dp_clip", type=float, default=1.0,
              help="algorithm=dp_fedavg: per-client update L2 clip S")
@click.option("--dp_noise_multiplier", type=float, default=1.0,
              help="algorithm=dp_fedavg: noise multiplier z (stddev z*S "
                   "on the clipped-update sum)")
@click.option("--dp_delta", type=float, default=1e-5,
              help="algorithm=dp_fedavg: report epsilon at this delta")
@click.option("--ditto_lambda", type=float, default=0.1,
              help="algorithm=ditto: proximal pull of each personal model "
                   "toward the global model (0 = purely local models)")
@click.option("--async_buffer_k", type=int, default=10,
              help="algorithm=fedbuff: server applies one staleness-"
                   "weighted step whenever this many client deltas have "
                   "buffered (no round barrier; comm_round counts steps)")
@click.option("--staleness_exp", type=float, default=0.5,
              help="algorithm=fedbuff: staleness discount (1+tau)^-exp")
@click.option("--async_server_lr", type=float, default=1.0,
              help="algorithm=fedbuff: global step scale eta_g")
@click.option("--enable_wandb", is_flag=True, default=False,
              help="Start a wandb run and mirror metric rows to it (ref "
                   "main_fedavg.py:93-108); no-op if wandb is not installed")
@click.option("--selection",
              type=click.Choice(("uniform", "weighted", "power_of_choice",
                                 "straggler_aware")),
              default="uniform",
              help="Client selection policy (scheduler/policies.py): "
                   "reference-parity uniform, sample-count weighted, "
                   "loss-biased power-of-choice (Cho et al. 2020), or "
                   "straggler-avoiding (telemetry health registry). "
                   "Round-keyed + seed-deterministic; uniform/weighted "
                   "select identical cohorts across runtimes (see "
                   "docs/SCHEDULING.md for the adaptive policies)")
@click.option("--overprovision_factor", type=float, default=1.0,
              help="Select ceil(k * factor) clients per round so "
                   "deadline/quorum rounds still close with ~k useful "
                   "uploads; transport runtimes spawn one worker per "
                   "overprovisioned slot (1.0 = off)")
@click.option("--fault_plan", type=str, default=None,
              help="Fault-injection plan (scheduler/faults.py): inline "
                   "JSON or a path to a JSON file — per-client dropout_p/"
                   "slowdown_s/crash_at_round/flaky_upload_p, plus device "
                   "profiles ('profiles'/'fleet' keys) and scripted "
                   "per-round events; 'trace:<path>' replays a recorded "
                   "fault_trace.json byte-identically (the file "
                   "--telemetry_dir writes). Deterministic per (plan "
                   "seed, client, round). Sync transport runs with "
                   "participation faults require --deadline_s")
@click.option("--send_retries", type=int, default=0,
              help="Transport runtimes: retry a failed send up to N times "
                   "under seed-deterministic jittered exponential backoff "
                   "(core/retry.py; at-least-once — FedBuff/sync servers "
                   "dedupe re-deliveries). 0 = fail on first error. "
                   "Retry/give-up counts land in summary.json "
                   "(comm/retries, comm/gave_up) and Prometheus")
@click.option("--send_backoff_s", type=float, default=0.05,
              help="Retry backoff base in seconds (doubles per retry, "
                   "jittered, capped at CommConfig.send_backoff_max_s)")
@click.option("--send_timeout_s", type=float, default=30.0,
              help="runtime=grpc: per-RPC send deadline (was hard-coded "
                   "30 s). With --send_retries the retry layer owns "
                   "reconnects, so first contact also fails fast at this "
                   "timeout and retries instead of the one-shot 120 s "
                   "wait_for_ready handshake")
@click.option("--send_fault_p", type=float, default=0.0,
              help="Transport chaos: fail each send ATTEMPT with this "
                   "probability before it reaches the wire — "
                   "deterministic in (seed, send seq, attempt), so a "
                   "flaky-transport run replays identically; the "
                   "surviving attempt delivers exactly once (numerics "
                   "unchanged). Requires --send_retries >= 1")
@click.option("--deadline_s", type=float, default=0.0,
              help="Transport runtimes: straggler deadline — after this many "
                   "seconds the server closes the round on a quorum instead "
                   "of waiting forever (0 = ref-parity wait-for-all)")
@click.option("--min_clients", type=int, default=1,
              help="Minimum uploads required to close a deadline round")
@click.option("--compression", type=click.Choice(("none", "int8", "int4", "topk", "topk8")),
              default="none",
              help="Transport runtimes: compress the client uplink update "
                   "(core/compression.py) — int8/int4 (nibble-packed) "
                   "quantization, top-k sparsification, or topk8 (top-k "
                   "with int8 values) of the round delta")
@click.option("--downlink_compression", type=click.Choice(("none", "int8")),
              default="none",
              help="Transport runtimes: quantize the server->client model "
                   "broadcast int8 (encoded ONCE per round, shared across "
                   "the cohort; ~4x downlink cut). The server keeps the "
                   "dequantized tree as the round's reference, so both "
                   "wire ends train/decode against the identical model; "
                   "metered as comm/downlink_* in summary.json")
@click.option("--topk_frac", type=float, default=0.01,
              help="compression=topk/topk8: fraction of entries kept per tensor")
@click.option("--error_feedback", is_flag=True, default=False,
              help="Lossy codecs (topk/topk8/int4/int8): per-client residual "
                   "memory (EF-SGD) so dropped coordinates and quantization "
                   "error ship in later rounds; practically mandatory for "
                   "the 4-bit grid")
@click.option("--secure_agg", is_flag=True, default=False,
              help="Transport runtimes: pairwise-masked uploads — the "
                   "server only ever sums masked field vectors (ref "
                   "turboaggregate); quorum rounds recover dropout masks")
@click.option("--beacons/--no_beacons", default=True,
              help="Transport runtimes: piggyback a bounded (~200 B) client "
                   "telemetry beacon on each model upload — measured "
                   "train/encode seconds, retry count, codec, DeviceProfile "
                   "tier (telemetry/wire.py). Feeds the server's health "
                   "registry, flight recorder phase splits, and the "
                   "per-tier fleet digests (/fleet, fedml_fleet_*). "
                   "Observability only: numerics are byte-identical with "
                   "beacons off; overhead is metered separately as "
                   "comm/beacon_bytes and never counted as model payload")
@click.option("--warmup", is_flag=True, default=False,
              help="AOT-compile the run's programs before round 0 "
                   "(fedml_tpu/compile/warmup.py): round/eval/server "
                   "programs on vmap/mesh, the shared client local-train "
                   "on loopback/shm/mqtt (so --deadline_s rounds start "
                   "with compilation already paid). Emits compile "
                   "telemetry spans + per-program XLA cost analysis into "
                   "summary.json; numerics are identical to a cold run")
@click.option("--compile_cache_dir", type=click.Path(path_type=Path), default=None,
              help="Directory of the hardened persistent XLA compile cache "
                   "(fedml_tpu/compile/persistent.py: atomic writes, "
                   "sha256 integrity verification with quarantine, "
                   "advisory file lock). Default <checkout>/.jax_cache; "
                   "where JAX_COMPILATION_CACHE_DIR is set that directory "
                   "is used and this flag only warns. Cache hit/miss/"
                   "quarantine counts land in summary.json (compile/*)")
@click.option("--executable_cache", type=click.Path(path_type=Path), default=None,
              help="Persist SERIALIZED AOT executables at this directory "
                   "(executables/ under JAX_COMPILATION_CACHE_DIR where "
                   "that is set; compile/executable_cache.py, served "
                   "through the hardened store): --warmup exports every "
                   "executable it compiles, and a fresh process deserializes its whole "
                   "warmup set instead of compiling — zero-cold-start "
                   "restarts/replicas/CI shards. Keyed by program digest "
                   "+ shape class + environment fingerprint, so jaxlib/"
                   "backend/code skew recompiles cleanly. Deserialize "
                   "counts land in summary.json (compile/deserialize_*)")
@click.option("--compile_cache_min_s", type=float, default=2.0,
              help="Only persist HLO compiles at least this slow into "
                   "--compile_cache_dir (default 2.0 — the conservative "
                   "threshold tests/conftest.py uses). 0 persists every "
                   "compile: combined with --executable_cache this is the "
                   "zero-cold-start setting where a repeat process "
                   "reports compile/recompiles == 0")
@click.option("--recompile_budget", type=int, default=None,
              help="Fail the run when more than this many XLA compiles "
                   "happen (fedml_tpu/analysis/sentinel.py) — the tripwire "
                   "for cache-key instabilities that silently recompile "
                   "every round. Counts every ACTUAL backend compile incl. "
                   "small utility programs (persistent-cache hits and "
                   "deserialized executables are not compiles and don't "
                   "count — a fully warm process passes budget 0), so pick "
                   "a coarse upper bound; the observed count always lands "
                   "in summary.json (compile/recompiles). Off by default")
@click.option("--device_slice", type=int, default=-1,
              help="Serve-layer placement pin (AdminConfig.device_slice): "
                   "run this tenant on slice N of the service's device "
                   "slices (serve --device_slices; docs/SERVING.md). -1 = "
                   "bin-pack onto the least-loaded slice. Single runs "
                   "ignore it — the flag exists so tenant-spec keys stay "
                   "the single-run flag surface")
@click.option("--admit_min_headroom_mb", type=float, default=0.0,
              help="Serve-layer admission requirement: refuse this tenant "
                   "when host MemAvailable is below this many MB at the "
                   "admission door (serve/admission.py). 0 = none; single "
                   "runs ignore it")
@click.option("--admit_cost_cap_gflops", type=float, default=0.0,
              help="Serve-layer admission cap: refuse when the tenant's "
                   "priced compute (measured XLA cost-analysis flops x "
                   "cohort) exceeds this many GFLOP/round. 0 = none; "
                   "single runs ignore it")
@click.option("--rank", type=int, default=None,
              help="runtime=grpc: this process's rank (0 = server, 1..K = "
                   "clients; ref main_fedavg_rpc.py --fl_worker_index). "
                   "Client ranks run on the host CPU: a chip belongs to "
                   "one process, and it is not a simulated edge device")
@click.option("--ip_config", type=click.Path(path_type=Path), default=None,
              help="runtime=grpc: CSV rank,ip table (ref grpc_ipconfig.csv); "
                   "default localhost for all ranks")
@click.option("--base_port", type=int, default=8890)
@click.option("--ci", is_flag=True, default=False, help="CI short-circuit (1 round smoke)")
def main(**opt):
    """Train a federated model on TPU."""
    # returned for in-process callers (main(args, standalone_mode=False));
    # click discards it when run as a program
    return run(**opt)


def _dp_cfg(opt):
    if opt["algorithm"] != "dp_fedavg":
        return None
    from fedml_tpu.privacy import DpConfig

    clip = opt.get("dp_clip", 1.0)
    z = opt.get("dp_noise_multiplier", 1.0)
    delta = opt.get("dp_delta", 1e-5)
    # parse-time validation: z<=0 would otherwise crash the accountant
    # after data/model setup, and a negative clip would silently INVERT
    # every client update (scale = clip/norm < 0)
    if clip <= 0:
        raise click.UsageError("--dp_clip must be > 0")
    if z <= 0:
        raise click.UsageError(
            "--dp_noise_multiplier must be > 0 (no-noise runs are not DP; "
            "use --algorithm fedavg instead)"
        )
    if not 0.0 < delta < 1.0:
        raise click.UsageError("--dp_delta must be in (0, 1)")
    return DpConfig(clip_norm=clip, noise_multiplier=z, delta=delta)


def _validate_scheduler(config, opt) -> None:
    """Parse-time scheduler/fault-plan validation — a malformed plan or an
    unsatisfiable combination must fail before minutes of data/model
    setup, not as a mid-run hang."""
    from fedml_tpu.scheduler import FaultPlan

    if config.fed.overprovision_factor < 1.0:
        raise click.UsageError("--overprovision_factor must be >= 1.0")
    try:
        plan = FaultPlan.from_config(config)
    except ValueError as e:
        raise click.UsageError(f"--fault_plan: {e}")
    scheduler_engaged = (
        config.fed.selection != "uniform"
        or config.fed.overprovision_factor != 1.0
        or plan is not None
    )
    if opt["algorithm"] == "dp_fedavg" and scheduler_engaged:
        raise click.UsageError(
            "--selection/--overprovision_factor/--fault_plan cannot be "
            "combined with algorithm=dp_fedavg: its cohort is the "
            "run-seeded secret Poisson draw (privacy amplification by "
            "subsampling, privacy/dp_fedavg.py), which bypasses the "
            "scheduler — the flags would be silently ignored"
        )
    if opt["algorithm"] in _LONGTAIL and scheduler_engaged:
        # the long-tail drivers run their own fixed loops (uniform
        # sampling or no sampling at all) — accepting the flags there
        # would silently do nothing
        raise click.UsageError(
            "--selection/--overprovision_factor/--fault_plan have no "
            f"effect for algorithm={opt['algorithm']}: it drives its own "
            "fixed training loop outside the scheduler (supported: the "
            "FedAvg family, fedbuff, hierarchical, fedavg_robust)"
        )
    if config.fed.overprovision_factor != 1.0 and config.comm.secure_agg:
        raise click.UsageError(
            "--overprovision_factor and --secure_agg are incompatible: "
            "clients size the mask registry from client_num_per_round, so "
            "an overprovisioned worker set would not cancel its masks"
        )
    if config.fed.overprovision_factor != 1.0 and opt["algorithm"] == "fedbuff":
        raise click.UsageError(
            "--overprovision_factor is a synchronous quorum-round concept "
            "(select extra clients so deadline rounds close with ~k useful "
            "uploads); fedbuff has no rounds to overprovision — its "
            "workers stream continuously"
        )
    if (
        plan is not None
        and plan.has_participation_faults()
        and opt["runtime"] in ("loopback", "mqtt", "shm", "grpc")
        and opt["algorithm"] != "fedbuff"
        and not config.fed.deadline_s
    ):
        raise click.UsageError(
            "--fault_plan with dropout_p/crash_at_round on a synchronous "
            "transport requires --deadline_s: the all-received barrier "
            "would wait forever for the dropped upload"
        )


def _validate_comm_retry(config, opt) -> None:
    """Parse-time transport-retry validation: chaos without retries is a
    guaranteed mid-run crash, and the vmap/mesh runtimes exchange no
    messages for the flags to act on."""
    comm = config.comm
    if not 0.0 <= comm.send_fault_p < 1.0:
        raise click.UsageError("--send_fault_p must be in [0, 1)")
    if comm.send_retries < 0:
        raise click.UsageError("--send_retries must be >= 0")
    if comm.send_fault_p > 0 and comm.send_retries < 1:
        raise click.UsageError(
            "--send_fault_p injects transient send failures; without "
            "--send_retries >= 1 the first injected failure kills the "
            "sending actor instead of exercising the retry path"
        )
    if comm.send_timeout_s <= 0:
        raise click.UsageError("--send_timeout_s must be > 0")
    if (comm.send_retries or comm.send_fault_p) and opt["runtime"] in (
        "vmap", "mesh"
    ):
        raise click.UsageError(
            "--send_retries/--send_fault_p apply to the transport "
            "runtimes (loopback/shm/grpc/mqtt); vmap/mesh rounds exchange "
            "no messages, so the flags would be silently ignored"
        )


# Algorithms whose round-0 programs warmup_api/warmup_local_train can
# actually enumerate: the standard FedAvgAPI round/eval/server-step family.
# scaffold/ditto/dp_fedavg/hierarchical run bespoke train_round loops
# (their _build_round_fn is None or their cohorts reshape per group/draw),
# so warming there would either no-op or compile a program the run never
# dispatches — strictly worse than no flag. split_nn joined in PR 19:
# its fused/boundary/eval programs are digested ProgramCache factories
# warmed by compile/warmup.py:warmup_splitnn before round 0.
_WARMUP_ALGOS = (
    "fedavg", "fedprox", "fedopt", "fednova", "qfedavg", "fedavg_robust",
    "split_nn",
)


def _validate_compile(config, opt) -> None:
    """--warmup covers the algorithm×runtime combinations whose round-0
    programs can be enumerated up front; anywhere else the flag would
    silently do nothing (or waste a compile) — fail at parse time
    instead."""
    if not config.compile.warmup:
        return
    if opt["algorithm"] == "fedbuff":
        raise click.UsageError(
            "--warmup is not supported for algorithm=fedbuff: its workers "
            "stream continuously and compile on first dispatch; there is "
            "no round-0 barrier to warm against"
        )
    if opt["algorithm"] not in _WARMUP_ALGOS:
        raise click.UsageError(
            f"--warmup is not supported for algorithm={opt['algorithm']}: "
            "its driver builds its programs inside its own training loop, "
            "so there is no round-0 program to enumerate up front "
            f"(supported: {', '.join(_WARMUP_ALGOS)} on vmap/mesh and the "
            "sync transports)"
        )
    if opt["runtime"] == "grpc":
        raise click.UsageError(
            "--warmup is not supported for runtime=grpc: each client "
            "process owns its own programs — run the warmup in-process "
            "via the loopback/shm runtimes, or rely on a shared "
            "--compile_cache_dir to carry compiles across processes"
        )


def _log_compile(logger, baseline, restore=None, sentinel=None) -> None:
    """Forward the run's compile-cache activity (program dedup hits/misses
    + hardened persistent-layer counters) into summary.json — the CI
    oracle the ci.sh warmup smoke asserts on — then reinstate the
    pre-run persistent-cache binding (the row must be logged FIRST: it
    reads the run's installed cache). Called from the run() finally
    blocks so a crashed run can't leave its per-run cache installed in
    a long-lived process; the restore itself is exception-proof. A
    --recompile_budget sentinel is stopped and its counters logged here
    (observability first — the budget CHECK happens later, outside the
    finally, so the raise can't mask the run's own failure)."""
    from fedml_tpu.compile import compile_summary_row

    try:
        if sentinel is not None:
            sentinel.stop()
            logger.log(sentinel.summary_row())
        logger.log(compile_summary_row(baseline))
    finally:
        if restore is not None:
            restore()


def _check_sentinel(sentinel) -> None:
    """Enforce --recompile_budget after the run's telemetry has flushed:
    exceeding the budget fails the CLI run loudly (exit code 1) with the
    per-program compile events in the message."""
    if sentinel is None:
        return
    from fedml_tpu.analysis.sentinel import RecompileBudgetExceeded

    try:
        sentinel.check()
    except RecompileBudgetExceeded as e:
        raise click.ClickException(str(e))


def _checked_buffer_k(opt) -> int:
    """fedbuff's buffer size, validated at parse time (a 0/negative k would
    otherwise surface as a mid-run ValueError after data/model setup); 0
    for every synchronous algorithm."""
    if opt["algorithm"] != "fedbuff":
        return 0
    k = opt.get("async_buffer_k", 10)
    if k <= 0:
        raise click.UsageError("--algorithm fedbuff needs --async_buffer_k > 0")
    return k


def build_config(opt) -> RunConfig:
    return RunConfig(
        data=DataConfig(
            dataset=opt["dataset_name"],
            data_dir=str(opt["data_dir"]),
            partition_method=opt["partition_method"],
            partition_alpha=opt["partition_alpha"],
            batch_size=opt["batch_size"],
            pad_bucket=opt["pad_bucket"],
            device_cache=not opt.get("no_device_cache", False),
        ),
        fed=FedConfig(
            client_num_in_total=opt["client_num_in_total"],
            client_num_per_round=opt["client_num_per_round"],
            comm_round=1 if opt["ci"] else opt["comm_round"],
            epochs=opt["epochs"],
            frequency_of_the_test=opt["frequency_of_the_test"],
            ci=opt["ci"],
            group_num=opt["group_num"],
            group_comm_round=opt["group_comm_round"],
            eval_on_clients=opt.get("eval_on_clients", False),
            deadline_s=opt.get("deadline_s", 0.0),
            min_clients=opt.get("min_clients", 1),
            selection=opt.get("selection", "uniform"),
            overprovision_factor=opt.get("overprovision_factor", 1.0),
            fault_plan=opt.get("fault_plan") or "",
            client_parallelism=opt.get("client_parallelism", "auto"),
            async_buffer_k=_checked_buffer_k(opt),
            async_staleness_exp=opt.get("staleness_exp", 0.5),
            async_server_lr=opt.get("async_server_lr", 1.0),
            state_store=opt.get("state_store", "auto"),
            state_budget_bytes=opt.get("state_budget_bytes", 8 << 30),
            state_dir=opt.get("state_dir", ""),
            pipeline=opt.get("pipeline", "auto"),
        ),
        train=TrainConfig(
            client_optimizer=opt["client_optimizer"],
            lr=opt["lr"],
            wd=opt["wd"],
            momentum=opt["momentum"],
            prox_mu=opt["prox_mu"] if opt["algorithm"] == "fedprox" else 0.0,
            compute_dtype=opt.get("compute_dtype", "float32"),
            augment=opt.get("augment", "none"),
        ),
        server=ServerConfig(
            server_optimizer=opt["server_optimizer"],
            server_lr=opt["server_lr"],
            server_momentum=opt["server_momentum"],
        ),
        comm=CommConfig(
            compression=opt.get("compression", "none"),
            downlink_compression=opt.get("downlink_compression", "none"),
            topk_frac=opt.get("topk_frac", 0.01),
            error_feedback=opt.get("error_feedback", False),
            secure_agg=opt.get("secure_agg", False),
            send_retries=opt.get("send_retries", 0) or 0,
            send_backoff_s=opt.get("send_backoff_s", 0.05),
            send_timeout_s=opt.get("send_timeout_s", 30.0),
            send_fault_p=opt.get("send_fault_p", 0.0) or 0.0,
            beacons=opt.get("beacons", True),
        ),
        mesh=MeshConfig(client_shards=opt["client_shards"]),
        compile=CompileConfig(
            warmup=opt.get("warmup", False),
            cache_dir=str(opt.get("compile_cache_dir") or ""),
            min_compile_time_s=opt.get("compile_cache_min_s", 2.0),
            executable_cache=str(opt.get("executable_cache") or ""),
            recompile_budget=opt.get("recompile_budget"),
        ),
        admin=AdminConfig(
            device_slice=int(
                opt["device_slice"]
                if opt.get("device_slice") is not None else -1
            ),
            admit_min_headroom_mb=float(
                opt.get("admit_min_headroom_mb", 0.0) or 0.0
            ),
            admit_cost_cap_gflops=float(
                opt.get("admit_cost_cap_gflops", 0.0) or 0.0
            ),
        ),
        model=opt["model"],
        seed=opt["seed"],
    )


def _telemetry_start(opt, config=None):
    """Start run-scoped telemetry sinks (the tracer itself is always on —
    spans cost microseconds; these flags decide whether anything is
    EXPORTED). Returns an opaque state for _telemetry_finish, or None when
    no telemetry flag is set. ``config`` supplies the flight-recorder
    ring bounds (PopulationConfig.flight_*)."""
    if opt.get("prom_port") is None and opt.get("telemetry_dir") is None:
        return None
    from fedml_tpu.telemetry import FlightRecorder, get_comm_meter, get_tracer

    # run-scoped trace + comm totals: the exported trace.json and the
    # summary.json telemetry row describe THIS run, not whatever earlier
    # runs happened in the same process (CliRunner tests, notebook sweeps)
    get_tracer().reset()
    # fleet digests (telemetry/wire.py): per-tier latency percentiles fed
    # by client beacons — run-scoped like the tracer, for the same reason
    from fedml_tpu.telemetry import get_fleet

    get_fleet().reset()
    state = {"exporter": None, "comm_baseline": get_comm_meter().snapshot()}
    # flight recorder (telemetry/flight.py): fold the run's round spans
    # into the bounded last-K ring — flight/* summary block + flight.json
    # under --telemetry_dir, p50/p95 gauges under --prom_port
    from fedml_tpu.analysis.sentinel import global_recompiles

    flight_kw = dict(
        comm_meter=get_comm_meter(), recompiles_fn=global_recompiles
    )
    state["flight"] = (
        FlightRecorder.from_config(config, **flight_kw)
        if config is not None else FlightRecorder(**flight_kw)
    ).attach(get_tracer())
    if opt.get("prom_port") is not None:
        from fedml_tpu.telemetry import PrometheusExporter

        # compile observability (satellite of fedml_tpu/analysis/): the
        # ProgramCache publishes its hit/miss/bypass gauges on every
        # event; the XLA backend-compile gauge needs the process-wide
        # monitoring listener installed — do it whenever metrics are
        # actually exported, not only under --recompile_budget
        from fedml_tpu.analysis.sentinel import ensure_backend_listener

        ensure_backend_listener()

        state["exporter"] = PrometheusExporter(port=opt["prom_port"]).start()
        # /fleet: the live per-tier beacon digest snapshot, next to
        # /metrics (serve runs get it via RoundIntrospection.install)
        state["exporter"].add_route(
            "/fleet", lambda _path: (200, get_fleet().snapshot())
        )
        click.echo(
            f"telemetry: prometheus metrics on "
            f"http://127.0.0.1:{state['exporter'].port}/metrics",
            err=True,
        )
    return state


def _telemetry_finish(state, opt, logger, health=None):
    """Flush run telemetry: forward comm totals into MetricsLogger (so
    summary.json stays the single CI oracle), write the Chrome trace +
    health registry snapshot into --telemetry_dir, stop the exporter.
    Idempotent — the run paths call it on success (with the runtime's
    health registry) and again from their exception backstop (a crashed
    run must still flush its trace: that is exactly when you want it)."""
    if state is None or state.get("done"):
        return
    state["done"] = True
    from fedml_tpu.telemetry import get_fleet, get_tracer, telemetry_summary

    logger.log(telemetry_summary(baseline=state.get("comm_baseline")))
    fleet_row = get_fleet().summary_row()
    if fleet_row.get("fleet/beacons"):
        logger.log(fleet_row)  # the fleet/* summary block (beacon digests)
    flight = state.get("flight")
    if flight is not None:
        logger.log(flight.summary_row())  # the flight/* summary block
        flight.detach()
    tdir = opt.get("telemetry_dir")
    if tdir:
        tdir = Path(tdir)
        tdir.mkdir(parents=True, exist_ok=True)
        suffix = _telemetry_suffix(opt)
        trace_path = tdir / f"trace{suffix}.json"
        get_tracer().write_chrome_trace(str(trace_path))
        if flight is not None:
            with open(tdir / f"flight{suffix}.json", "w") as f:
                json.dump(
                    {
                        "rounds_folded": flight.rounds_folded,
                        "ring_capacity": flight.capacity,
                        "percentiles": flight.percentiles(),
                        "records": flight.tail(),
                    },
                    f, indent=2,
                )
        if health is not None:
            with open(tdir / f"health{suffix}.json", "w") as f:
                json.dump(health.snapshot(), f, indent=2)
            if hasattr(health, "export_trace") and opt.get("algorithm") != "fedbuff":
                # the observed fleet as a replayable FaultTrace
                # (scheduler/faults.py): --fault_plan trace:<this file>
                # re-injects the exact recorded dropout/slowdown/flaky
                # events, byte-identically (docs/SCHEDULING.md). FedBuff
                # records fault events keyed by DISPATCH TAG, not round —
                # such a trace cannot replay faithfully, so none is
                # written (trace replay targets the round-keyed runtimes)
                health.export_trace(
                    rounds=1 if opt.get("ci") else opt.get("comm_round")
                ).save(str(tdir / f"fault_trace{suffix}.json"))
        click.echo(f"telemetry: wrote {trace_path}", err=True)
    if state.get("exporter") is not None:
        state["exporter"].stop()


def _telemetry_suffix(opt) -> str:
    """Disambiguate telemetry files when several processes share one
    --telemetry_dir: gRPC ranks get .rankN, multi-host SPMD processes get
    .hostK (each then merges cleanly in Perfetto — the tracks are already
    labeled per host). Single-process runs keep the bare names."""
    rank = opt.get("rank")
    if rank is not None:
        return f".rank{rank}"
    try:
        import jax

        if jax.process_count() > 1:
            return f".host{jax.process_index()}"
    except Exception:  # noqa: BLE001 — backend-less finalize must not fail
        pass
    return ""


def run(**opt):
    # Platform: jax honours JAX_PLATFORMS (and XLA_FLAGS) by itself, read
    # once when the backend initialises — `JAX_PLATFORMS=cpu XLA_FLAGS=
    # --xla_force_host_platform_device_count=8` gives CLI mesh runs the
    # virtual device farm examples/ci.sh relies on; unset, jax takes the
    # TPU and fails at start-up if it cannot. Nothing is re-applied here.
    if opt["runtime"] == "grpc" and opt.get("rank"):
        # One process per chip: a gRPC CLIENT rank (1..K) simulates an edge
        # device and trains on the host CPU; the chip belongs to whichever
        # single process the operator gives it to (rank 0, or a vmap/mesh
        # run). Pinned before this process's first backend touch.
        import jax

        jax.config.update("jax_platforms", "cpu")
    from fedml_tpu.data import registry as data_registry
    from fedml_tpu.models import create_model
    from fedml_tpu.utils import MetricsLogger, save_checkpoint
    from fedml_tpu.utils.profiling import trace

    config = build_config(opt)
    # validate DP flags BEFORE data/model setup (a z<=0 would otherwise
    # surface as a mid-run crash after minutes of dataset loading); the
    # result is rebuilt at the _build_api call site
    _dp_cfg(opt)
    _validate_scheduler(config, opt)
    _validate_compile(config, opt)
    _validate_comm_retry(config, opt)
    # BEFORE any jit: every compile of this run is eligible for the
    # hardened persistent store (compile/persistent.py), at the directory
    # resolve_cache_dir picks — $JAX_COMPILATION_CACHE_DIR, else
    # --compile_cache_dir, else <checkout>/.jax_cache. install_run_cache
    # hands back a restore() that reinstates the previous binding when the
    # run completes, so a run embedded in a long-lived process can't
    # hijack later compiles onto its cache dir.
    from fedml_tpu.compile import install_run_cache

    _, restore_compile_cache = install_run_cache(
        config.compile.cache_dir,
        min_compile_time_secs=config.compile.min_compile_time_s,
    )
    if config.compile.executable_cache:
        # serialized-executable store (zero-cold-start): like the HLO
        # cache above, installed run-scoped with a composed restore so a
        # crashed/embedded run can't leave it bound process-wide
        from fedml_tpu.compile import install_run_executable_cache

        _, _restore_exec = install_run_executable_cache(
            config.compile.executable_cache
        )
        _restore_hlo = restore_compile_cache

        def restore_compile_cache() -> None:  # noqa: F811 — composed restore
            _restore_exec()
            _restore_hlo()

    from fedml_tpu.compile import compile_snapshot

    # baseline for the summary.json compile row: a run embedded in a
    # long-lived process (CliRunner tests, sweeps) reports ITS cache
    # activity, not the process's lifetime totals
    compile_baseline = compile_snapshot()
    sentinel = None
    if config.compile.recompile_budget is not None:
        # --recompile_budget: watch every XLA backend compile from here
        # to the end of the run (fedml_tpu/analysis/sentinel.py); the
        # check fires after telemetry flushes, via _check_sentinel
        from fedml_tpu.analysis.sentinel import RecompileSentinel

        if config.compile.recompile_budget < 0:
            raise click.UsageError("--recompile_budget must be >= 0")
        sentinel = RecompileSentinel(
            budget=config.compile.recompile_budget, label="cli"
        ).start()
    try:
        if opt["runtime"] in ("vmap", "mesh"):
            if config.comm.compression != "none":
                raise click.UsageError(
                    "--compression applies to the transport runtimes "
                    "(loopback/shm/grpc/mqtt); the vmap/mesh runtimes exchange "
                    "no messages, so the flag would be silently ignored"
                )
            if config.comm.downlink_compression != "none":
                raise click.UsageError(
                    "--downlink_compression applies to the transport runtimes "
                    "(loopback/shm/grpc/mqtt); the vmap/mesh runtimes exchange "
                    "no messages, so the flag would be silently ignored"
                )
            if config.fed.deadline_s or config.fed.min_clients != 1:
                raise click.UsageError(
                    "--deadline_s/--min_clients apply to the transport runtimes "
                    "(loopback/shm/grpc/mqtt); vmap/mesh rounds are one SPMD "
                    "program with no uploads to time out on"
                )
        elif config.fed.min_clients != 1 and not config.fed.deadline_s:
            raise click.UsageError(
                "--min_clients only takes effect after a --deadline_s deadline "
                "passes; without one the server still waits for every client"
            )
        if config.comm.secure_agg:
            if opt["runtime"] in ("vmap", "mesh"):
                raise click.UsageError(
                    "--secure_agg applies to the transport runtimes "
                    "(loopback/shm/grpc/mqtt)"
                )
            if config.comm.compression != "none":
                raise click.UsageError(
                    "--secure_agg and --compression are mutually exclusive: "
                    "masked field vectors cannot be sparsified/quantized"
                )
            if config.comm.downlink_compression != "none":
                raise click.UsageError(
                    "--secure_agg and --downlink_compression are mutually "
                    "exclusive: masked uploads are field vectors over the "
                    "exact broadcast reference, which requantizing would break"
                )
        if config.comm.error_feedback:
            from fedml_tpu.core.compression import EF_METHODS

            if config.comm.compression not in EF_METHODS:
                raise click.UsageError(
                    "--error_feedback is a residual memory for lossy codecs; "
                    f"it requires --compression in {EF_METHODS}"
                )
            if config.fed.deadline_s:
                raise click.UsageError(
                    "--error_feedback assumes every upload is aggregated, but "
                    "--deadline_s quorum rounds can discard late uploads — the "
                    "shipped (and residual-cleared) coordinates would be lost"
                )
            if (
                opt["runtime"] == "grpc"
                and config.fed.client_num_per_round != config.fed.client_num_in_total
            ):
                raise click.UsageError(
                    "--error_feedback under runtime=grpc requires full "
                    "participation (client_num_per_round == client_num_in_total): "
                    "residuals live per process and cannot follow a client that "
                    "the sampler re-assigns to another rank"
                )
        data = data_registry.load(config)
        task = data_registry.task_for_dataset(config.data.dataset)
        sample_shape = tuple(data.client_x[0].shape[1:])
        model = create_model(config.model, config.data.dataset, sample_shape, data.num_classes)

        poison_spec = attack_cfg = None
        if opt.get("attack", "none") == "backdoor":
            if opt["algorithm"] != "fedavg_robust" or opt["runtime"] != "vmap":
                raise click.UsageError(
                    "--attack backdoor requires --algorithm fedavg_robust "
                    "--runtime vmap"
                )
            from fedml_tpu.data.edge_cases import PoisonSpec, poison_clients
            from fedml_tpu.robustness.backdoor import AttackConfig

            k = opt.get("num_attackers", 1)
            if not 0 < k < data.num_clients:
                raise click.UsageError(
                    f"--num_attackers must be in [1, {data.num_clients - 1}]"
                )
            poison_spec = PoisonSpec(
                target_label=opt.get("target_label", 0),
                poison_frac=opt.get("poison_frac", 0.5),
            )
            # attacker ids derived ONCE — the poisoned shards and the boosted
            # uploads must target the same client set
            attack_cfg = AttackConfig(
                attacker_ids=tuple(range(k)),
                boost=opt.get("attack_boost", 10.0),
            )
            data = poison_clients(
                data, attacker_ids=attack_cfg.attacker_ids, spec=poison_spec,
                seed=config.seed,
            )

        if opt.get("enable_wandb"):
            from fedml_tpu.utils.metrics import wandb_init

            wandb_init(
                name=f"{opt['algorithm']}-r{opt['comm_round']}"
                f"-e{opt['epochs']}-lr{opt['lr']}",
                config={k: str(v) for k, v in opt.items()},
            )
        logger = MetricsLogger(
            str(opt["log_dir"]) if opt["log_dir"] else None,
            use_wandb=opt.get("enable_wandb", False),
        )
        telemetry = _telemetry_start(opt, config)
        api_cell = []

        def log_fn(row):
            logger.log(row)
            # crash-resumable: persist on every test round, not just at the end.
            # round_idx convention = "next round to run": row["round"] just
            # completed, so the continuation starts at row["round"] + 1.
            if opt["checkpoint_path"] and "Test/Acc" in row and api_cell:
                api = api_cell[0]
                gv = getattr(api, "global_vars", None)
                if gv is not None:
                    save_checkpoint(
                        str(opt["checkpoint_path"]),
                        gv,
                        round_idx=row["round"] + 1,
                        server_opt_state=getattr(api, "server_opt_state", None),
                        algo_state=getattr(
                            api, "checkpoint_state", lambda: None
                        )(),
                        sched_state=_sched_state(api),
                    )

        _validate_variant(opt)
        if opt["runtime"] == "grpc":
            # true multi-process federation: this process is ONE participant
            # (ref main_fedavg_rpc.py per-process drivers + run_*.sh launchers)
            if opt["algorithm"] not in ("fedavg", "fedprox", "fedopt", "fedbuff"):
                raise click.UsageError(
                    "runtime=grpc supports fedavg/fedprox/fedopt/fedbuff"
                )
            try:
                final, grpc_health = _run_grpc_process(
                    config, data, model, task, log_fn, opt
                )
                _telemetry_finish(telemetry, opt, logger, health=grpc_health)
            finally:
                _telemetry_finish(telemetry, opt, logger)
                _log_compile(
                    logger, compile_baseline, restore_compile_cache, sentinel
                )
            _check_sentinel(sentinel)
            logger.close()
            click.echo(json.dumps({k: _jsonable(v) for k, v in (final or {}).items()}))
            return None

        builder = _LONGTAIL.get(opt["algorithm"])
        if builder is not None:
            if opt["resume"]:
                raise click.UsageError(
                    f"--resume is not supported for algorithm={opt['algorithm']}"
                )
            allowed_runtimes = (
                ("vmap", "mesh") if opt["algorithm"] == "centralized" else ("vmap",)
            )
            if opt["runtime"] not in allowed_runtimes:
                raise click.UsageError(
                    f"algorithm={opt['algorithm']} supports only "
                    f"--runtime {'|'.join(allowed_runtimes)}"
                )
            if opt["checkpoint_path"] and opt["algorithm"] != "fedseg":
                # fail loudly rather than let a 50-round run discover at crash
                # time that nothing was ever saved
                raise click.UsageError(
                    f"--checkpoint_path is not supported for algorithm="
                    f"{opt['algorithm']} (supported: the FedAvg family and fedseg)"
                )
            try:
                with trace(str(opt["profile_dir"]) if opt["profile_dir"] else None):
                    final = builder(config, data, model, task, log_fn, opt)
            finally:
                # long-tail drivers have no per-client health registry; the
                # trace/comm totals still flush (on success AND on a crash)
                _telemetry_finish(telemetry, opt, logger)
                _log_compile(
                    logger, compile_baseline, restore_compile_cache, sentinel
                )
            _check_sentinel(sentinel)
            logger.close()
            click.echo(json.dumps({k: _jsonable(v) for k, v in (final or {}).items()}))
            return None

        api = _build_api(
            opt["algorithm"], opt["runtime"], config, data, model, task, log_fn,
            defense=opt.get("defense", "norm_diff_clipping"),
            num_byzantine=opt.get("num_byzantine", 1),
            multi_krum_m=opt.get("multi_krum_m", 3),
            norm_bound=opt.get("norm_bound", 5.0),
            noise_stddev=opt.get("noise_stddev", 0.025),
            attack_cfg=attack_cfg,
            ditto_lambda=opt.get("ditto_lambda", 0.1),
            dp_cfg=_dp_cfg(opt),
            qffl_q=opt.get("qffl_q", 1.0),
        )
        api_cell.append(api)

        if opt["resume"]:
            if opt["runtime"] in ("loopback", "mqtt", "shm"):
                raise click.UsageError(
                    f"--resume is not supported for runtime={opt['runtime']}"
                )
            _restore(api, opt)

        if config.compile.warmup and hasattr(api, "warmup"):
            # vmap/mesh: AOT-compile round/eval/server programs before round 0
            # (the transport _Runner has no .warmup — run_federation takes the
            # flag and warms the shared local-train program instead)
            api.warmup(log_fn=log_fn)

        try:
            with trace(str(opt["profile_dir"]) if opt["profile_dir"] else None):
                final = api.train()
            if getattr(api, "faults", None) is not None:
                # vmap/mesh fault accounting into summary.json (the transport
                # runners log their shared injector themselves)
                log_fn(api.faults.summary_row())
            if getattr(api, "pipeline_rounds", 0):
                # round pipeline: rounds whose host prep was hidden behind
                # the previous round's device dispatch (FedConfig.pipeline;
                # the per-round overlap seconds fold into flight/overlap_s)
                log_fn({"fed/pipeline_rounds": int(api.pipeline_rounds)})
            if poison_spec is not None:
                from fedml_tpu.data.edge_cases import attack_success_rate

                final = dict(final or {})
                final["Backdoor/ASR"] = attack_success_rate(
                    model, api.global_vars, data, poison_spec, eval_fn=api.eval_fn
                )
                # persist the attack metric alongside the per-round rows
                log_fn({
                    "round": config.fed.comm_round - 1,
                    "Backdoor/ASR": final["Backdoor/ASR"],
                })
            if opt["checkpoint_path"]:
                save_checkpoint(
                    str(opt["checkpoint_path"]),
                    getattr(api, "global_vars"),
                    round_idx=config.fed.comm_round,
                    server_opt_state=getattr(api, "server_opt_state", None),
                    algo_state=getattr(api, "checkpoint_state", lambda: None)(),
                    sched_state=_sched_state(api),
                )
            _telemetry_finish(
                telemetry, opt, logger, health=getattr(api, "health", None)
            )
        finally:
            # exception backstop: flush the trace and stop the exporter even
            # when the run crashed mid-train (idempotent after the call above);
            # the compile row + cache restore ride the same backstop so a
            # crashed run can't leave its per-run cache installed
            _telemetry_finish(telemetry, opt, logger)
            _log_compile(
                logger, compile_baseline, restore_compile_cache, sentinel
            )
        _check_sentinel(sentinel)
        logger.close()
        click.echo(json.dumps({k: _jsonable(v) for k, v in (final or {}).items()}))
        return api
    except BaseException:
        # a validation/setup failure BEFORE (or inside) a dispatch
        # path's own finally must not leave the per-run compile cache
        # installed process-wide (the CliRunner/sweep hijack the
        # install_run_cache docstring describes). restore() reinstates
        # a fixed prior snapshot, so paths that already restored via
        # _log_compile are unaffected by the second call.
        restore_compile_cache()
        if sentinel is not None:
            sentinel.stop()  # idempotent; drops the cache listener
        raise


_VARIANTS = {
    "decentralized": ("dsgd", "pushsum"),
    "fednas": ("first", "second"),
}


def _validate_variant(opt):
    v = opt.get("variant")
    if v is None:
        return
    allowed = _VARIANTS.get(opt["algorithm"])
    if allowed is None:
        raise click.UsageError(
            f"--variant has no meaning for algorithm={opt['algorithm']}"
        )
    if v not in allowed:
        raise click.UsageError(
            f"--variant for {opt['algorithm']} must be one of {allowed}, got {v!r}"
        )


def _jsonable(v):
    if isinstance(v, (np.floating, np.integer)):
        return v.item()
    return v


def _sched_state(api):
    """Scheduler RNG/selection state for the checkpoint's "sched" slot —
    a resumed run re-selects the in-flight round's cohort identically."""
    sched = getattr(api, "scheduler", None)
    return sched.state_dict() if sched is not None else None


def _restore(api, opt):
    """--resume: pour the checkpoint into the API and continue the round
    loop from the saved round (round-seeded sampling makes the continuation
    identical to the uninterrupted run — the kill-and-resume test relies on
    it)."""
    from fedml_tpu.utils.checkpoint import load_checkpoint, restore_like

    if not opt["checkpoint_path"]:
        raise click.UsageError("--resume requires --checkpoint_path")
    loaded_vars, round_idx, _, opt_state, algo_state, sched_state = load_checkpoint(
        str(opt["checkpoint_path"])
    )
    api.global_vars = restore_like(api.global_vars, loaded_vars)
    api.start_round = int(round_idx)
    # Server optimizer state (FedOpt family): restore so Adam/Yogi moments
    # survive the crash — per-round RNG is derived from (seed, round) and
    # needs no persistence.
    if opt_state is not None and getattr(api, "server_opt_state", None) is not None:
        api.server_opt_state = restore_like(api.server_opt_state, opt_state)
    # Algorithm-private state (SCAFFOLD control variates): without this a
    # resumed run silently degenerates to FedAvg until the variates
    # re-learn, breaking the identical-continuation contract above.
    # Scheduler selection memo + loss map: without it a resumed
    # power_of_choice run would re-derive the in-flight cohort from an
    # empty loss map and select differently than the uninterrupted run.
    if sched_state is not None and getattr(api, "scheduler", None) is not None:
        api.scheduler.load_state_dict(sched_state)
    if hasattr(api, "restore_state"):
        if algo_state is None:
            raise click.UsageError(
                "checkpoint has no algorithm state but "
                f"{type(api).__name__} needs it to resume faithfully — "
                "it was written by an older version or a different "
                "algorithm; restarting from round 0 is the only sound "
                "continuation"
            )
        api.restore_state(algo_state)


def _build_api(algorithm, runtime, config, data, model, task, log_fn,
               defense="norm_diff_clipping", num_byzantine=1, multi_krum_m=3,
               norm_bound=5.0, noise_stddev=0.025, attack_cfg=None,
               ditto_lambda=0.1, dp_cfg=None, qffl_q=1.0):
    from fedml_tpu.robustness import RobustConfig

    # one RobustConfig for whichever runtime's robust API is selected —
    # vmap and mesh must see identical defense parameters
    robust = RobustConfig(
        defense_type=defense,
        norm_bound=norm_bound,
        stddev=noise_stddev,
        num_byzantine=num_byzantine,
        multi_krum_m=multi_krum_m,
    )
    if runtime in ("loopback", "mqtt", "shm"):
        if algorithm == "fedbuff":
            from fedml_tpu.algorithms import fedbuff as FB

            runner_fn = {
                "loopback": FB.run_fedbuff_loopback,
                "shm": FB.run_fedbuff_shm,
                "mqtt": FB.run_fedbuff_mqtt,
            }[runtime]

            class _AsyncRunner:
                global_vars = None
                server_opt_state = None
                start_round = 0
                health = None

                def train(self):
                    server = runner_fn(
                        config, data, model, task=task, log_fn=log_fn,
                    )
                    self.global_vars = server.global_vars
                    self.health = server.health
                    return server.history[-1] if server.history else {}

            return _AsyncRunner()
        if algorithm not in ("fedavg", "fedprox", "fedopt"):
            raise click.UsageError(
                f"runtime={runtime} supports fedavg/fedprox/fedopt/fedbuff"
            )
        from fedml_tpu.algorithms.fedavg_transport import (
            run_loopback_federation,
            run_mqtt_federation,
            run_shm_federation,
        )

        runner_fn = {
            "mqtt": run_mqtt_federation,
            "shm": run_shm_federation,
            "loopback": run_loopback_federation,
        }[runtime]

        class _Runner:
            global_vars = None
            server_opt_state = None
            start_round = 0
            health = None

            def train(self):
                server = runner_fn(
                    config, data, model, task=task, log_fn=log_fn,
                    server_opt=algorithm == "fedopt",
                    warmup=config.compile.warmup,
                )
                self.global_vars = server.global_vars
                # expose the FedOpt moments so --checkpoint_path persists
                # them (the vmap --resume path restores from this slot)
                self.server_opt_state = server._server_opt_state
                self.health = server.health
                return server.history[-1] if server.history else {}

        return _Runner()

    if algorithm == "fedbuff":
        raise click.UsageError(
            "algorithm=fedbuff is an async TRANSPORT protocol — run it "
            "with --runtime loopback, shm, or mqtt"
        )
    if runtime == "mesh":
        from fedml_tpu.parallel import DistributedFedAvgAPI, DistributedFedOptAPI

        if algorithm == "fedopt":
            return DistributedFedOptAPI(
                config, data, model, task=task, log_fn=log_fn
            )
        if algorithm == "fedavg_robust":
            from fedml_tpu.parallel import RobustDistributedFedAvgAPI

            return RobustDistributedFedAvgAPI(
                config, data, model, task=task, log_fn=log_fn, robust=robust
            )
        if algorithm == "fednova":
            from fedml_tpu.parallel import DistributedFedNovaAPI

            return DistributedFedNovaAPI(
                config, data, model, task=task, log_fn=log_fn
            )
        if algorithm == "scaffold":
            from fedml_tpu.parallel import DistributedScaffoldAPI

            return DistributedScaffoldAPI(
                config, data, model, task=task, log_fn=log_fn
            )
        if algorithm == "ditto":
            from fedml_tpu.parallel import DistributedDittoAPI

            return DistributedDittoAPI(
                config, data, model, task=task, log_fn=log_fn,
                lam=ditto_lambda,
            )
        if algorithm == "dp_fedavg":
            from fedml_tpu.parallel import DistributedDPFedAvgAPI

            return DistributedDPFedAvgAPI(
                config, data, model, task=task, log_fn=log_fn, dp=dp_cfg,
            )
        if algorithm == "hierarchical":
            from fedml_tpu.parallel import HierarchicalShardedAPI

            # default mesh = hybrid groups×clients from config.fed.group_num
            return HierarchicalShardedAPI(
                config, data, model, task=task, log_fn=log_fn
            )
        if algorithm not in ("fedavg", "fedprox"):
            raise click.UsageError(
                "runtime=mesh currently supports fedavg/fedprox/fedopt/"
                "fednova/scaffold/ditto/dp_fedavg/hierarchical/fedavg_robust"
            )
        return DistributedFedAvgAPI(config, data, model, task=task, log_fn=log_fn)

    # vmap simulator runtimes (ref standalone/*)
    if algorithm in ("fedavg", "fedprox"):
        from fedml_tpu.algorithms import FedAvgAPI

        return FedAvgAPI(config, data, model, task=task, log_fn=log_fn)
    if algorithm == "fedopt":
        from fedml_tpu.algorithms import FedOptAPI

        return FedOptAPI(config, data, model, task=task, log_fn=log_fn)
    if algorithm == "fednova":
        from fedml_tpu.algorithms import FedNovaAPI

        return FedNovaAPI(config, data, model, task=task, log_fn=log_fn)
    if algorithm == "scaffold":
        from fedml_tpu.algorithms.scaffold import ScaffoldAPI

        return ScaffoldAPI(config, data, model, task=task, log_fn=log_fn)
    if algorithm == "ditto":
        from fedml_tpu.algorithms.ditto import DittoAPI

        return DittoAPI(
            config, data, model, task=task, log_fn=log_fn, lam=ditto_lambda,
        )
    if algorithm == "dp_fedavg":
        from fedml_tpu.privacy import DpConfig, DPFedAvgAPI

        return DPFedAvgAPI(
            config, data, model, task=task, log_fn=log_fn, dp=dp_cfg or DpConfig(),
        )
    if algorithm == "qfedavg":
        from fedml_tpu.algorithms.qfedavg import QFedAvgAPI

        return QFedAvgAPI(
            config, data, model, task=task, log_fn=log_fn, q=qffl_q,
        )
    if algorithm == "hierarchical":
        from fedml_tpu.algorithms import HierarchicalFedAvgAPI

        return HierarchicalFedAvgAPI(config, data, model, task=task, log_fn=log_fn)
    if algorithm == "fedavg_robust":
        from fedml_tpu.algorithms.fedavg_robust import RobustFedAvgAPI

        if attack_cfg is not None:
            from fedml_tpu.robustness.backdoor import BackdoorFedAvgAPI

            return BackdoorFedAvgAPI(
                config, data, model, task=task, log_fn=log_fn, robust=robust,
                attack=attack_cfg,
            )
        return RobustFedAvgAPI(
            config, data, model, task=task, log_fn=log_fn, robust=robust,
        )
    raise click.UsageError(f"unknown algorithm {algorithm}")


# ---------------------------------------------------------------------------
# Long-tail drivers: algorithms whose APIs are not FedAvgAPI-shaped. Each
# takes the standard flag surface and runs a complete training loop
# (replacing ref drivers main_fedgkt.py, main_fedgan.py, main_fednas.py,
# main_split_nn.py, main_vfl.py, main_decentralized.py, TA_main).
# ---------------------------------------------------------------------------


def _client_shards_list(data, limit=None):
    ids = range(data.num_clients if limit is None else min(limit, data.num_clients))
    return [(data.client_x[i], data.client_y[i]) for i in ids]


def _run_fedgkt(config, data, model, task, log_fn, opt):
    from fedml_tpu.algorithms.fedgkt import FedGKTAPI

    shape = tuple(data.client_x[0].shape[1:])
    api = FedGKTAPI(
        num_classes=data.num_classes,
        input_shape=shape,
        lr=config.train.lr,
        seed=config.seed,
    )
    clients = _client_shards_list(data, config.fed.client_num_per_round)
    cache = None
    final = {}
    for r in range(config.fed.comm_round):
        cache = api.train_round(
            clients,
            local_epochs=config.fed.epochs,
            server_epochs=config.fed.epochs,
            batch_size=config.data.batch_size,
            server_logits_cache=cache,
        )
        acc = api.evaluate(data.test_x, data.test_y, client_id=0)
        final = {"round": r, "Test/Acc": float(acc)}
        log_fn(final)
    return final


def _run_fedgan(config, data, model, task, log_fn, opt):
    from fedml_tpu.algorithms.fedgan import FedGANAPI

    api = FedGANAPI(config, data, log_fn=log_fn)
    return api.train()


def _run_fedseg(config, data, model, task, log_fn, opt):
    from fedml_tpu.algorithms.fedseg import FedSegAPI

    api = FedSegAPI(
        config,
        data,
        model,
        checkpoint_path=str(opt["checkpoint_path"]) if opt["checkpoint_path"] else None,
        log_fn=log_fn,
    )
    return api.train()


def _run_fednas(config, data, model, task, log_fn, opt):
    from fedml_tpu.algorithms.fednas import FedNASAPI

    shape = tuple(data.client_x[0].shape[1:])
    api = FedNASAPI(
        data,
        num_classes=data.num_classes,
        input_shape=shape,
        batch_size=config.data.batch_size,
        seed=config.seed,
        arch_grad=opt.get("variant") or "first",
    )
    final = {}
    for r in range(config.fed.comm_round):
        geno = api.train_round(
            r,
            client_num_per_round=config.fed.client_num_per_round,
            epochs=config.fed.epochs,
        )
        acc = api.evaluate(data.test_x, data.test_y)
        final = {"round": r, "Test/Acc": float(acc), "genotype": str(geno)}
        log_fn(final)
    return final


def _run_split_nn(config, data, model, task, log_fn, opt):
    from fedml_tpu.algorithms.split_nn import SplitNNAPI, default_split_models

    shape = tuple(data.client_x[0].shape[1:])
    bottom, top = default_split_models(shape, data.num_classes)
    if config.compile.warmup:
        # the split programs (fused step + boundary triple + eval) are
        # ProgramCache factories like the horizontal family's — --warmup
        # AOT-compiles them before round 0 (fedml_tpu/compile/warmup.py)
        from fedml_tpu.compile import warmup_splitnn

        warmup_splitnn(bottom, top, config, data, log_fn=log_fn)
    api = SplitNNAPI(
        bottom, top, lr=config.train.lr, momentum=config.train.momentum,
        wd=config.train.wd, seed=config.seed,
    )
    clients = _client_shards_list(data, config.fed.client_num_per_round)
    final = {}
    for r in range(config.fed.comm_round):
        api.train_ring(
            clients,
            batch_size=config.data.batch_size,
            epochs_per_client=config.fed.epochs,
        )
        acc = api.evaluate(data.test_x, data.test_y)
        final = {"round": r, "Test/Acc": float(acc)}
        log_fn(final)
    return final


def _run_vertical_fl(config, data, model, task, log_fn, opt):
    """VFL over a vertical (feature) split of the dataset: party 0 (guest)
    holds labels, the rest are hosts (ref classical_vertical_fl)."""
    from fedml_tpu.algorithms.vertical_fl import VFLAPI

    x = np.concatenate([cx.reshape(len(cx), -1) for cx in data.client_x], axis=0)
    y = (np.concatenate(data.client_y, axis=0) % 2).astype(np.float32)
    D = x.shape[1]
    splits = [D // 3, D // 3, D - 2 * (D // 3)]
    xs, off = [], 0
    for s in splits:
        xs.append(x[:, off : off + s])
        off += s
    api = VFLAPI(feature_splits=splits, lr=config.train.lr, seed=config.seed)
    final = {}
    for r in range(config.fed.comm_round):
        stats = api.train_epoch(xs, y, batch_size=config.data.batch_size)
        final = {"round": r, "Train/Loss": stats["loss"], "Train/Acc": stats["acc"]}
        log_fn(final)
    return final


def _run_decentralized(config, data, model, task, log_fn, opt):
    """Decentralized online learning over the client topology: each client's
    shard becomes its stream (ref standalone/decentralized)."""
    from fedml_tpu.algorithms.decentralized import DecentralizedAPI
    from fedml_tpu.models import ModelDef
    from fedml_tpu.models.linear import LogisticRegression
    from fedml_tpu.partition.topology import SymmetricTopologyManager

    N = data.num_clients
    T = min(len(cy) for cy in data.client_y)
    x = np.stack([cx[:T].reshape(T, -1) for cx in data.client_x])
    y = np.stack([(cy[:T] % 2).astype(np.float32) for cy in data.client_y])
    topo = SymmetricTopologyManager(N, neighbor_num=min(4, N - 1))
    topo.generate_topology()
    lrmodel = ModelDef(
        LogisticRegression(num_classes=1), (x.shape[-1],), 1, name="lr"
    )
    api = DecentralizedAPI(
        lrmodel,
        topo,
        lr=config.train.lr,
        variant=opt.get("variant") or "dsgd",
        seed=config.seed,
    )
    out = api.run(x, y)
    final = {
        "iterations": int(len(out["losses"])),
        "final_regret": float(out["regret"][-1]),
    }
    log_fn(final)
    return final


def _run_secagg(config, data, model, task, log_fn, opt):
    """One FedAvg round where the upload path goes through the secure
    aggregator (pairwise masking + dropout recovery): verifies the masked
    sum equals the plain sum (ref turboaggregate)."""
    from fedml_tpu.secagg.secure_aggregation import SecureAggregator

    K = config.fed.client_num_per_round
    updates = [
        data.client_x[i].reshape(len(data.client_x[i]), -1).mean(axis=0)
        for i in range(min(K, data.num_clients))
    ]
    N, D = len(updates), len(updates[0])
    agg = SecureAggregator(N, D, seed=config.seed)
    active = list(range(N))
    uploads = {i: agg.client_upload(i, updates[i], active) for i in active}
    # drop one client after masking: survivors recover its masks
    dropped = None
    if N > 2:
        dropped = N - 1
        uploads.pop(dropped)
    total = agg.aggregate(uploads, intended=active)
    expect = np.sum([u for i, u in enumerate(updates) if i != dropped], axis=0)
    err = float(np.max(np.abs(total - expect)))
    final = {
        "clients": N,
        "dropped": dropped,
        "max_abs_error": err,
        "secure_sum_ok": bool(err < 1e-3),
    }
    log_fn(final)
    return final


def _run_centralized(config, data, model, task, log_fn, opt):
    """Non-federated data-parallel baseline (ref
    fedml_experiments/centralized/main.py DDP path): --runtime mesh shards
    the batch over all devices; --comm_round doubles as the epoch count."""
    from fedml_tpu.train.centralized import CentralizedTrainer

    mesh = None
    if opt["runtime"] == "mesh":
        from fedml_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(opt["client_shards"], "batch")
    trainer = CentralizedTrainer(
        config, data, model, task=task, mesh=mesh, log_fn=log_fn
    )
    return trainer.train()


def _run_grpc_process(config, data, model, task, log_fn, opt):
    """One federation participant over gRPC: rank 0 = server FSM, rank 1..K
    = client actor. Every process loads the same config/data (deterministic
    partition from the shared seed), mirroring the reference's
    one-process-per-worker model (FedAvgAPI.py:14-27). Returns
    ``(final_row, health)`` — health is the server's client registry on
    rank 0 (fed by broadcast→upload round-trips), None on client ranks."""
    from fedml_tpu.algorithms.fedavg_transport import (
        FedAvgClientManager,
        FedAvgServerManager,
        LocalTrainer,
    )
    from fedml_tpu.core.grpc_comm import GrpcCommManager, read_ip_config

    rank = opt["rank"]
    if rank is None:
        raise click.UsageError("runtime=grpc requires --rank")
    # one worker per scheduler slot (overprovisioned cohorts need
    # ceil(k * factor) client processes — launch scripts must match)
    from fedml_tpu.scheduler import overprovisioned_k

    K = overprovisioned_k(
        config.fed.client_num_per_round,
        config.fed.overprovision_factor,
        config.fed.client_num_in_total,
    )
    if opt["ip_config"]:
        table = read_ip_config(str(opt["ip_config"]))
    else:
        table = {r: "127.0.0.1" for r in range(K + 1)}
    comm = GrpcCommManager(
        rank, table, base_port=opt["base_port"],
        send_timeout_s=config.comm.send_timeout_s,
        max_workers=config.comm.grpc_max_workers,
        stream_budget=config.comm.grpc_stream_budget,
        max_message_mb=config.comm.grpc_max_message_mb,
        keepalive_s=config.comm.grpc_keepalive_s,
    )
    # per-process fault injector (client ranks only): the plan is
    # deterministic in (seed, client, round), so every process injects
    # the same faults; the server infers dropouts from its quorum rounds
    from fedml_tpu.scheduler import FaultInjector

    faults = FaultInjector.from_config(config) if rank != 0 else None
    if opt["algorithm"] == "fedbuff":
        from fedml_tpu.algorithms.fedbuff import (
            FedBuffClientManager,
            FedBuffServerManager,
        )

        if rank == 0:
            server = FedBuffServerManager(
                config, comm, model, data=data, task=task, worker_num=K,
                log_fn=log_fn,
            )
            server.send_init_msg()
            server.run()
            return (
                server.history[-1] if server.history else {}
            ), server.health
        client = FedBuffClientManager(
            config, comm, rank,
            LocalTrainer(
                config, data, model, task,
                straggle_s=opt.get("straggle_ms", 0.0) / 1e3,
            ),
            faults=faults,
        )
        client.run()
        if faults is not None:
            # per-process fault accounting (this rank's summary.json) —
            # the in-process runners log their shared injector instead
            log_fn(faults.summary_row())
        if client.orphaned:
            raise click.ClickException(
                f"async worker rank {rank} orphaned: server unreachable "
                "and no FINISH within its deadline"
            )
        return {"rank": rank, "finished": True}, None
    if rank == 0:
        server = FedAvgServerManager(
            config, comm, model, data=data, task=task, worker_num=K,
            log_fn=log_fn, server_opt=opt["algorithm"] == "fedopt",
        )
        server.send_init_msg()
        server.run()
        if server.deadline_error is not None:
            # release the client processes before surfacing the failure —
            # they would otherwise park on their inboxes
            from fedml_tpu.core.message import Message, MessageType as MT

            for worker in range(1, K + 1):
                try:
                    server.send_message(Message(MT.FINISH, 0, worker))
                except Exception:  # noqa: BLE001
                    pass
            raise RuntimeError(
                "server deadline path failed"
            ) from server.deadline_error
        if opt.get("checkpoint_path"):
            # rank 0 owns the converged params — persist them so gRPC runs
            # can be compared/resumed like the in-process runtimes (the CI
            # wire-fleet gate diffs these arrays across beacons on/off)
            from fedml_tpu.utils import save_checkpoint

            save_checkpoint(
                str(opt["checkpoint_path"]),
                server.global_vars,
                round_idx=config.fed.comm_round,
                server_opt_state=getattr(server, "server_opt_state", None),
            )
        return (server.history[-1] if server.history else {}), server.health
    client = FedAvgClientManager(
        config, comm, rank,
        LocalTrainer(
            config, data, model, task,
            straggle_s=opt.get("straggle_ms", 0.0) / 1e3,
        ),
        faults=faults,
    )
    client.run()
    if faults is not None:
        log_fn(faults.summary_row())  # this rank's summary.json
    return {"rank": rank, "finished": True}, None


_LONGTAIL = {
    "centralized": _run_centralized,
    "fedgkt": _run_fedgkt,
    "fedgan": _run_fedgan,
    "fedseg": _run_fedseg,
    "fednas": _run_fednas,
    "split_nn": _run_split_nn,
    "vertical_fl": _run_vertical_fl,
    "decentralized": _run_decentralized,
    "secagg": _run_secagg,
}


if __name__ == "__main__":
    main()
