"""The paper's own WikiText-2 LSTM LM (Table III) as an arch.

embedding 33278x1024 -> 2-layer LSTM(hidden 1024) -> decoder tied to the
embedding. Table III's 84.98M parameters count an untied 33278x1024
decoder (+ its bias): 2 * 33278*1024 + 2 * (8*1024^2 + 4*1024) + 33278
~= 84.97M. Tied, as here, the model holds 33278*1024 + 2 * (8*1024^2 +
4*1024) ~= 50.9M parameters (the table is padded to 33280 rows)."""
from .base import ArchConfig

CONFIG = ArchConfig(
    name="lstm_wikitext2", family="lstm",
    n_layers=2, d_model=1024, n_heads=0, kv_heads=0, d_ff=0, vocab=33278,
    rope="none", supports_long=True,  # O(1) recurrent state
    tie_embeddings=True,
    source="paper Table III (WikiText-2, 84.98M params)",
    notes="2-layer LSTM hidden 1024, tied embeddings: 33278*1024 + "
          "2*(8*1024^2 + 4*1024) ~= 50.9M (84.97M with an untied decoder).",
)
