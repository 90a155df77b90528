from attention_tpu.models.attention_layer import (  # noqa: F401
    GQASelfAttention,
    KVCache,
    QuantKVCache,
    RaggedKVCache,
    RollingKVCache,
)
from attention_tpu.models.cross_attention import GQACrossAttention  # noqa: F401
from attention_tpu.models.moe import LatentExperts, MoEMLP  # noqa: F401
from attention_tpu.models.mamba import Mamba2Mixer  # noqa: F401
from attention_tpu.models.pipeline import (  # noqa: F401
    make_pipelined_train_step,
    pipelined_forward,
)
from attention_tpu.models.resilient import train_with_recovery  # noqa: F401
from attention_tpu.models.seq2seq import (  # noqa: F401
    TinySeq2Seq,
    generate_seq2seq,
    seq2seq_loss,
)
from attention_tpu.models.speculative import generate_speculative  # noqa: F401
from attention_tpu.models.linear_attention import GatedDeltaNet  # noqa: F401
from attention_tpu.models.transformer import TransformerBlock, TinyDecoder  # noqa: F401
from attention_tpu.models.config import decoder_from_config  # noqa: F401
from attention_tpu.models.decode import (  # noqa: F401
    decode_step,
    generate,
    generate_beam,
    generate_paged,
    generate_ragged,
    prefill,
)
