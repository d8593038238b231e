from .dist_sort import DIST_MIN_TOTAL, sample_merge_k, sample_sort  # noqa: F401
from .sharding import (  # noqa: F401
    Parallelism,
    batch_pspecs,
    build_param_pspecs,
    cache_pspecs,
    dist_sort_axis,
    make_parallelism,
    to_named,
    vocab_topk_axis,
)
