from repro.kernels.flash_attention.ops import (decode_walk,  # noqa: F401
                                               flash_attention,
                                               paged_decode_attention)
from repro.kernels.flash_attention.ref import (ref_attention,  # noqa: F401
                                               ref_paged_decode_attention)
