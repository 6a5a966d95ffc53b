"""Elastic fleet subsystem: the topology changes while you run.

Three coordinated pieces (ROADMAP item 4):

- `trainer.ElasticTrainer` — preemption-tolerant training: on replica
  loss/gain (heartbeat `membership.MembershipView` or chaos `preempt`
  rules), re-shards ZeRO optimizer state to the surviving mesh via the
  canonical layout (parallel/zero.py, arXiv 2004.13336) and continues with
  momentum intact — no checkpoint-and-halt.
- `autoscaler.AutoscaleController` — serving autoscale: FleetFrontend
  health/load signals evaluated through the AlertEngine machinery against
  a declarative `AutoscalePolicy` JSON, spawning/draining ServingServer
  replicas through the `launcher.ReplicaLauncher` SPI (in-process threads
  for tests, subprocesses for smoke), deploys fanned so new replicas come
  up warm.
- `tools/loadgen.py` — the open-loop arrival-process load generator that
  measures the scale claims (fixed offered rate, no coordinated omission,
  latency SLO report).

Every transition (replica lost, re-shard, scale-up, drain) is visible in
/fleet/* and the structured logs with trace correlation, and gated through
alert-style lifecycle rules like canary deploys.
"""
from .autoscaler import AutoscaleController, AutoscalePolicy
from .launcher import (InProcessLauncher, ReplicaLauncher,
                       SubprocessLauncher)
from .membership import MembershipView
from .trainer import ElasticImpossible, ElasticTrainer

__all__ = ["AutoscaleController", "AutoscalePolicy", "ElasticImpossible",
           "ElasticTrainer", "InProcessLauncher", "MembershipView",
           "ReplicaLauncher", "SubprocessLauncher"]
