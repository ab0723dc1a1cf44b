from .ops import (  # noqa: F401
    MAX_VMEM_KEYS,
    merge_base_runs_device,
    merge_pair_device,
    merge_sorted_device,
    merge_sorted_runs,
    merge_window_keys,
    sort_with_payload,
)
from .ref import merge_ranks_keys, merge_ranks_ref  # noqa: F401
from .merge_runs import merge_ranks_pallas  # noqa: F401
