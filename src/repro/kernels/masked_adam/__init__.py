from repro.kernels.masked_adam import ops  # noqa: F401
from repro.kernels.masked_adam.kernel import (LANES,  # noqa: F401
                                              masked_adam_kernel,
                                              masked_adam_stacked)
from repro.kernels.masked_adam.ops import (PackMeta,  # noqa: F401
                                           block_group_ids,
                                           block_mask_for_group,
                                           block_masks_for_plan,
                                           fused_masked_adam, pack,
                                           pack_stacked, plan_block_mask,
                                           unpack, unpack_stacked)
