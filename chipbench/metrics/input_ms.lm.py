"""Host milliseconds per step that the program's input encoder
(``repro.lower.one_hot_rows``) spends on the pre-drawn token ids, timed
inside the harness's ``batch_fn``."""

from chipbench.readers import encode_ms as read  # noqa: F401
