from repro.core.a3po import (  # noqa: F401
    alpha_from_staleness,
    compute_prox_logp_approximation,
    compute_prox_logp_kl_adaptive,
    kl_adaptive_alpha,
    staleness,
)
from repro.core.advantages import (  # noqa: F401
    broadcast_over_tokens,
    group_normalized_advantages,
)
from repro.core.objective import (  # noqa: F401
    coupled_ppo_loss,
    decoupled_ppo_loss,
    fused_a3po_loss,
    resolve_alpha,
)
from repro.core.algorithms import (  # noqa: F401
    Algorithm,
    LossInputs,
    available,
    get_algorithm,
    register,
    registry_table,
    resolve_algorithm,
)
