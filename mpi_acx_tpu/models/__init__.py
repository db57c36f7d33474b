"""Model families built on the parallel substrate.

The reference ships no models (SURVEY.md §0: "it is not a training
framework") — but its driver-defined target configs are model workloads
(GPT-2 125M and Llama-style pipeline exchanges). These are those workloads, TPU-native: MXU-shaped matmuls in
bfloat16, static shapes, and parallelism expressed through the
mpi_acx_tpu.parallel primitives.
"""

from mpi_acx_tpu.models.transformer import (  # noqa: F401
    TransformerConfig,
    gpt2_small,
    tiny_config,
    init_params,
    forward,
    loss_fn,
    init_kv_cache,
    prefill,
    decode_step,
    generate,
    cast_params,
)
from mpi_acx_tpu.models.moe import (  # noqa: F401
    MoeConfig,
    init_moe_params,
    load_balance_loss,
    make_moe_train_step,
    moe_layer,
    moe_layer_and_aux,
    router_z_loss,
)
from mpi_acx_tpu.models import llama  # noqa: F401  (namespaced: llama.forward, ...)
from mpi_acx_tpu.models import moe_transformer  # noqa: F401  (namespaced)
from mpi_acx_tpu.models.speculative import (  # noqa: F401
    speculative_generate,
    speculative_sample,
)
from mpi_acx_tpu.models.serving import (  # noqa: F401
    serve_greedy,
    serve_sample,
)
