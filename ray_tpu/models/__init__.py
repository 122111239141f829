"""ray_tpu.models: flagship model families, TPU-first.

Pure-jax parameter pytrees with logical sharding axes (no framework
classes): the same model runs single-chip, TP, FSDP, or SP by swapping
partition rule tables (ray_tpu.parallel.partition)."""

from .gpt import (  # noqa: F401
    GPTConfig,
    gpt_forward,
    gpt_init,
    gpt_loss,
    gpt_param_axes,
    make_train_step,
)
from .hybrid import (  # noqa: F401
    HybridConfig,
    hybrid_forward,
    hybrid_init,
    hybrid_loss,
    hybrid_param_axes,
    make_hybrid_train_step,
)
from .lfm2_moe import (  # noqa: F401
    Lfm2MoeConfig,
    lfm2_moe_forward,
    lfm2_moe_init,
    lfm2_moe_loss,
    lfm2_moe_loss_and_counters,
    lfm2_moe_param_axes,
    make_lfm2_moe_train_step,
)
from .llama import (  # noqa: F401
    LlamaConfig,
    llama_forward,
    llama_init,
    llama_loss,
    llama_param_axes,
    make_llama_train_step,
)
from .moe import (  # noqa: F401
    MoEConfig,
    make_moe_train_step,
    moe_forward,
    moe_init,
    moe_loss,
    moe_loss_and_counters,
    moe_param_axes,
)
from .nemotron_h import (  # noqa: F401
    NemotronHConfig,
    make_nemotron_h_train_step,
    nemotron_h_forward,
    nemotron_h_init,
    nemotron_h_loss,
    nemotron_h_loss_and_counters,
    nemotron_h_param_axes,
)
from .olmo_hybrid import (  # noqa: F401
    OlmoHybridConfig,
    make_olmo_hybrid_train_step,
    olmo_hybrid_forward,
    olmo_hybrid_init,
    olmo_hybrid_loss,
    olmo_hybrid_param_axes,
)
from .resnet import (  # noqa: F401
    ResNetConfig,
    make_predictor,
    resnet_forward,
    resnet_init,
    resnet_param_axes,
)
from .sambay import (  # noqa: F401
    SambaYConfig,
    make_sambay_train_step,
    sambay_forward,
    sambay_init,
    sambay_loss,
    sambay_param_axes,
)
from .vit import (  # noqa: F401
    ViTConfig,
    make_classifier,
    make_vit_train_step,
    vit_forward,
    vit_init,
    vit_loss,
    vit_param_axes,
)
from .xing4 import (  # noqa: F401
    Xing4Config,
    make_xing4_train_step,
    xing4_forward,
    xing4_init,
    xing4_loss,
    xing4_loss_and_counters,
    xing4_param_axes,
)
from .glm4_moe_lite import (  # noqa: F401
    Glm4MoeLiteConfig,
    glm4_moe_lite_forward,
    glm4_moe_lite_init,
    glm4_moe_lite_loss,
    glm4_moe_lite_loss_and_counters,
    glm4_moe_lite_param_axes,
    make_glm4_moe_lite_train_step,
)
from .keye_vl2 import (  # noqa: F401
    KeyeVL2Config,
    keye_vl2_forward,
    keye_vl2_init,
    keye_vl2_loss,
    keye_vl2_loss_and_counters,
    keye_vl2_param_axes,
    make_keye_vl2_train_step,
)
from .bailing_hybrid import (  # noqa: F401
    BailingHybridConfig,
    bailing_hybrid_forward,
    bailing_hybrid_init,
    bailing_hybrid_loss,
    bailing_hybrid_loss_and_counters,
    bailing_hybrid_param_axes,
    make_bailing_hybrid_train_step,
)
from .afmoe import (  # noqa: F401
    AfmoeConfig,
    afmoe_forward,
    afmoe_init,
    afmoe_loss,
    afmoe_loss_and_counters,
    afmoe_param_axes,
    make_afmoe_train_step,
)
