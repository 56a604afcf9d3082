from .decode import (decode_attention, decode_attention_live,
                     decode_attention_xla)
from .flash import flash_attention
from .ring import ring_attention
