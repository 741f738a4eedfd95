"""The paper's primary contribution: the fault-tolerant training
infrastructure (cluster failure model, health checks, alerting, gang
scheduling with buffer pool, two-tier storage, Young-interval checkpointing,
and the FT runtime composing them)."""
from repro.core.aiops import Anomaly, AnomalyDetector, render_dashboard
from repro.core.alerts import Alert, AlertManager, SlackSink
from repro.core.tenancy import Namespace, TenantScheduler
from repro.core.checkpoint import (CheckpointManager, latest_step,
                                   load_checkpoint, save_checkpoint)
from repro.core.clock import VirtualClock, WallClock
from repro.core.cluster import FailureKind, Node, NodeState, SimCluster
from repro.core.health import Autopilot
from repro.core.runtime import (FTTrainLoop, GoodputReport, job_mtbf_seconds,
                                simulate_job)
from repro.core.scheduler import GangScheduler, Job, JobState
from repro.core.storage import (COS, NFS, SCALE, BlobStore, ScaleCache,
                                StorageStack)
from repro.core.straggler import StragglerDetector
from repro.core.telemetry import MetricsRegistry, SpanLog
from repro.core.youngs import (checkpoint_every_n_steps, lost_fraction,
                               optimal_lost_fraction, young_interval)

__all__ = [
    "Anomaly", "AnomalyDetector", "render_dashboard", "Namespace",
    "TenantScheduler",
    "Alert", "AlertManager", "SlackSink", "CheckpointManager", "latest_step",
    "load_checkpoint", "save_checkpoint", "VirtualClock", "WallClock",
    "FailureKind", "Node", "NodeState", "SimCluster", "Autopilot",
    "FTTrainLoop", "GoodputReport", "job_mtbf_seconds", "simulate_job",
    "GangScheduler", "Job", "JobState", "COS", "NFS", "SCALE", "BlobStore",
    "ScaleCache", "StorageStack", "StragglerDetector",
    "MetricsRegistry", "SpanLog", "checkpoint_every_n_steps", "lost_fraction",
    "optimal_lost_fraction", "young_interval",
]
