"""Generated tokens delivered to clients in the window, over the window's length."""

from bench.readers import output_tok_s as read  # noqa: F401
