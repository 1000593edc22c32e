"""FL-DP³S core: data profiling, eq.-(14) similarity kernel, k-DPP selection.

The paper's primary contribution as a composable JAX module — see DESIGN.md §1.
"""

from repro.core.dpp import (
    KDPPSamplerState,
    elementary_symmetric,
    greedy_map_kdpp,
    kdpp_log_prob,
    kdpp_sampler_state,
    log_det_subset,
    sample_kdpp,
    sample_kdpp_from_eigh,
)
from repro.core.metrics import (
    cohort_label_distribution,
    gemd,
    label_distribution,
    label_distributions,
)
from repro.core.profiles import (
    fc1_profile,
    gradient_profile,
    profile_all_clients,
    profile_stacked_clients,
    representative_gradient_profile,
)
from repro.core.selection import (
    CandidateSet,
    ClusterSelection,
    DPPSelection,
    FedSAESelection,
    PowerOfChoiceSelection,
    RoundState,
    SelectionStrategy,
    UniformSelection,
    funnel_candidates,
    funnel_scores,
    make_strategy,
)
from repro.core.similarity import (
    candidate_kernel,
    dpp_kernel,
    kernel_from_profiles,
    pairwise_dists,
    pairwise_sq_dists,
    similarity_matrix,
)
