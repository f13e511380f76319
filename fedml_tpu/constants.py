"""Framework-wide constants.

Capability parity with reference `python/fedml/constants.py` (training types,
backends, federated optimizers) — redesigned for a single JAX/TPU engine.
"""

__version__ = "0.1.0"

# ---------------------------------------------------------------------------
# Training planes (reference: constants.py FEDML_TRAINING_PLATFORM_*)
# ---------------------------------------------------------------------------
TRAINING_PLATFORM_SIMULATION = "simulation"
TRAINING_PLATFORM_CROSS_SILO = "cross_silo"
TRAINING_PLATFORM_CROSS_DEVICE = "cross_device"
TRAINING_PLATFORM_CROSS_CLOUD = "cross_cloud"
TRAINING_PLATFORM_SERVING = "fedml_serving"

# ---------------------------------------------------------------------------
# Simulation backends.  The reference dispatches sp / MPI / NCCL
# (`runner.py:34-77`).  TPU-native equivalents:
#   sp      — host-driven sequential loop (debug / tiny configs)
#   parrot  — vectorized client batches (vmap/scan) on one device
#   mesh    — shard_map over a `clients` mesh axis (multi-chip, ICI collectives)
# ---------------------------------------------------------------------------
SIMULATION_BACKEND_SP = "sp"
SIMULATION_BACKEND_PARROT = "parrot"
SIMULATION_BACKEND_MESH = "mesh"
# hyperscale — streamed cohorts over a virtual 10⁵–10⁶-client population
# (double-buffered host→device staging, sharded per-client state)
SIMULATION_BACKEND_HYPERSCALE = "hyperscale"
SIMULATION_BACKENDS = (
    SIMULATION_BACKEND_SP,
    SIMULATION_BACKEND_PARROT,
    SIMULATION_BACKEND_MESH,
    SIMULATION_BACKEND_HYPERSCALE,
)

# Cross-silo / distributed transports (reference: fedml_comm_manager.py:131-209)
COMM_BACKEND_INPROC = "INPROC"       # in-process fake transport (new: for tests)
COMM_BACKEND_GRPC = "GRPC"
COMM_BACKEND_MQTT_S3 = "MQTT_S3"     # control/bulk split; object store pluggable

# Cross-silo scenarios (reference: __init__.py horizontal vs hierarchical)
CROSS_SILO_SCENARIO_HORIZONTAL = "horizontal"
CROSS_SILO_SCENARIO_HIERARCHICAL = "hierarchical"

# ---------------------------------------------------------------------------
# Federated optimizers (reference: algorithm dirs under simulation/sp/*)
# ---------------------------------------------------------------------------
FED_OPT_FEDAVG = "FedAvg"
FED_OPT_FEDAVG_SEQ = "FedAvg_seq"
FED_OPT_FEDOPT = "FedOpt"
FED_OPT_FEDPROX = "FedProx"
FED_OPT_FEDNOVA = "FedNova"
FED_OPT_FEDDYN = "FedDyn"
FED_OPT_SCAFFOLD = "SCAFFOLD"
FED_OPT_MIME = "Mime"
FED_OPT_HIERARCHICAL = "HierarchicalFL"
FED_OPT_VERTICAL = "VerticalFL"
FED_OPT_SPLIT_NN = "SplitNN"
FED_OPT_ASYNC_FEDAVG = "Async_FedAvg"
FED_OPT_SECAGG = "SA"
FED_OPT_LIGHTSECAGG = "LSA"
FED_OPT_DECENTRALIZED = "Decentralized"

SUPPORTED_FED_OPTIMIZERS = (
    FED_OPT_FEDAVG,
    FED_OPT_FEDAVG_SEQ,
    FED_OPT_FEDOPT,
    FED_OPT_FEDPROX,
    FED_OPT_FEDNOVA,
    FED_OPT_FEDDYN,
    FED_OPT_SCAFFOLD,
    FED_OPT_MIME,
    FED_OPT_HIERARCHICAL,
    FED_OPT_VERTICAL,
    FED_OPT_SPLIT_NN,
    FED_OPT_ASYNC_FEDAVG,
    FED_OPT_SECAGG,
    FED_OPT_LIGHTSECAGG,
    FED_OPT_DECENTRALIZED,
)

# Mesh axis names used across the parallel layer
AXIS_CLIENTS = "clients"   # federated client parallelism (the FL "DP")
AXIS_DATA = "data"         # intra-silo data parallelism (DDP equivalent)
AXIS_MODEL = "model"       # tensor parallelism
AXIS_SEQ = "seq"           # sequence/context parallelism (ring attention)
AXIS_EXPERT = "expert"     # expert parallelism (MoE)
AXIS_PIPE = "pipe"         # pipeline parallelism

# ---------------------------------------------------------------------------
# TPU chip peak bf16 FLOP/s by jax device_kind (public specs; MXU peak).
# Single source of truth for every MFU computation, read through
# `flight_recorder.chip_peak_flops`.  A kind that is not listed has no
# peak: the library then reports no MFU and the bench scripts refuse.
# ---------------------------------------------------------------------------
TPU_PEAK_BF16_FLOPS = {
    "TPU v5 lite": 197e12,   # v5e
    "TPU v5e": 197e12,
    "TPU v4": 275e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,   # v6e/Trillium
}
