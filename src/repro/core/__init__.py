# The paper's primary contribution: Reduced-Set KPCA (Algorithm 1) driven by
# the shadow density estimate (Algorithm 2), plus every baseline the paper
# compares against and the §5 error-bound machinery.
from repro.core.kernels_math import (  # noqa: F401
    DEFAULT_BACKEND, DEFAULT_PRECISION, Kernel, gaussian, laplacian,
    make_kernel, gram_matrix, gram_matrix_dense, weighted_gram,
    pairwise_sq_dists, kde, rsde_eval,
)
from repro.core.shadow import (  # noqa: F401
    shadow_select, shadow_select_np, shadow_select_host,
    shadow_select_blocked, two_level_merge,
)
from repro.core.rsde import (  # noqa: F401
    RSDE, make_rsde, shadow_rsde, kmeans_rsde, kmeans_rsde_stream,
    paring_rsde, herding_rsde,
)
from repro.core.rskpca import (  # noqa: F401
    KPCAModel, fit, fit_rskpca, fit_kpca, fit_subsampled_kpca,
    embedding_alignment_error, eigenvalue_error,
)
from repro.core.pipeline import fit_centers  # noqa: F401
from repro.core.ingest_pipeline import (  # noqa: F401
    IngestStats, ingest_fit, pad_block, select_streaming,
)
from repro.core.nystrom import (  # noqa: F401
    fit_nystrom, fit_nystrom_stream, fit_weighted_nystrom,
    fit_weighted_nystrom_stream,
)
from repro.core.random_features import (  # noqa: F401
    RFFKPCAModel, fit_rff, fit_rff_stream, sample_rff,
)
from repro.core.methods import (  # noqa: F401
    METHODS, MethodSpec, fit_stream, select_method,
)
from repro.core import mmd  # noqa: F401
from repro.core.mmd import (  # noqa: F401
    weight_update_bound, absorb_bound, insert_bound, remove_bound,
)
from repro.core.kmla import (  # noqa: F401
    reduced_laplacian_eigenmaps, reduced_diffusion_maps,
)
