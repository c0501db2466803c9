"""Decentralised federated runtime: vectorised node-ensemble trainer + serving."""
from .executor import (
    CheckpointPolicy,
    TrajectoryConfig,
    run_elastic_trajectory,
    run_event_trajectory,
    run_sharded_trajectory,
    run_sweep,
    run_trajectory,
    run_warmup_sweep,
    run_warmup_trajectory,
    stack_states,
    unstack_states,
)
from .router import QueryStream, Router, hop_matrix, make_router, poisson_query_stream
from .serve import (
    ServeEngine,
    consensus_params,
    decode_one,
    generate,
    generate_tokenwise,
    prefill,
    run_serve_trajectory,
    serve_summary,
)
from .trainer import (
    DFLState,
    dfl_round,
    init_fl_state,
    make_eval_fn,
    make_round_fn,
    sigma_metrics,
    train_loop,
)
