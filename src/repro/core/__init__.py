"""MATADOR core: the Tsetlin Machine and its boolean-to-silicon compiler."""

from repro.core.tm import (  # noqa: F401
    TMConfig,
    TMState,
    accuracy,
    class_sums,
    clause_outputs,
    include_mask,
    init,
    literals,
    polarity,
    predict,
    vote_matrix,
)
from repro.core.compiler import (  # noqa: F401
    CompiledTM,
    CompileStats,
    compile_tm,
    predict_compiled,
    run_compiled,
)
from repro.core.train import eval_step, fit, train_step  # noqa: F401

