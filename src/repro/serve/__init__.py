"""Online round engine: incremental sessions + streaming federated serving.

Two layers over the SAME single-round bodies the scan substrates execute
(`repro.core.rounds.ROUND_DEFS` + the per-algorithm `*_step_def` builders):

* `open_session` / `FedSession` — a sweep held open: `session.step(n)` runs n
  rounds of every trial with device-resident state, `session.run_until(eps)`
  early-stops; k incremental rounds == the first k columns of `run_batch`.
* `FedRoundServer` / `ClientStream` / `ServeStats` — a streaming simulation:
  clients churn on a stream, cohorts form on the fly from resident clients,
  rounds run continuously with pipelined stats readback (rounds/sec,
  p50/p95/p99 round latency, dist-to-opt over wall-clock).
* `SessionPool` — multi-tenant serving: many same-shaped sessions packed into
  ONE stacked device-resident state and advanced by a single jitted dispatch
  per tick, each tenant's trajectory equal to its standalone `FedSession`;
  `FedRoundServer(pool=...)` drives it with the same pipelined readback.

Not to be confused with `repro.launch.serve`, the model-decode batch server.
"""
from repro.serve.pool import SessionPool
from repro.serve.server import ClientStream, FedRoundServer
from repro.serve.session import FedSession, open_session, trial_step_def
from repro.serve.stats import PipelinedReadback, ServeStats

__all__ = [
    "ClientStream",
    "FedRoundServer",
    "FedSession",
    "PipelinedReadback",
    "ServeStats",
    "SessionPool",
    "open_session",
    "trial_step_def",
]
