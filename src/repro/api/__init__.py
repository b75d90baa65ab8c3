"""repro.api — the single public API over the decomposition stack.

    config.py    RunConfig = DataConfig + PlanConfig + MethodConfig +
                 ExecConfig + ObsConfig + ServeConfig: frozen, validated,
                 JSON-round-trippable
    executor.py  ExecutorSpec registry (local / dist / streaming) + the one
                 method-capability gate (require_capability)
    session.py   Session.from_config -> .ingest() -> .plan() -> .fit() ->
                 .serve_handle(), lazy cached stages, checkpoint resume;
                 run(cfg) one-shot
    cli.py       python -m repro {ingest,plan,fit,serve,dryrun} and the
                 --list-methods / --list-impls capability matrices

Everything else under ``repro.*`` is machinery this API drives
(core/plan/ingest/methods/dist/checkpoint/serve/obs) or the launchers over
it (``repro.launch``); new callers should enter through this package.
"""
from .config import (ConfigError, DataConfig, ExecConfig, MethodConfig,
                     ObsConfig, PlanConfig, RunConfig, ServeConfig)
from .executor import (EXECUTORS, ExecutorSpec, executor_matrix, get_executor,
                       register_executor, require_capability)
from .session import ServeHandle, Session, run

__all__ = [
    "ConfigError", "DataConfig", "PlanConfig", "MethodConfig", "ExecConfig",
    "ObsConfig", "ServeConfig", "RunConfig",
    "EXECUTORS", "ExecutorSpec", "executor_matrix", "get_executor",
    "register_executor", "require_capability",
    "ServeHandle", "Session", "run",
]
