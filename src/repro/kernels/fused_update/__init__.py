from repro.kernels.fused_update.ops import (flat_accumulate,
                                            flat_apply_groups,
                                            init_flat_opt_state)

__all__ = ["flat_accumulate", "flat_apply_groups", "init_flat_opt_state"]
