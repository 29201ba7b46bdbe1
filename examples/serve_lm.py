"""Serving example: batched prefill + decode with sharded KV caches.

Run:  PYTHONPATH=src python examples/serve_lm.py
"""

from repro.launch import serve as serve_launch
from repro.runtime import enable_compilation_cache


if __name__ == "__main__":
    enable_compilation_cache()
    serve_launch.main(
        [
            "--arch", "qwen3-0.6b", "--reduce", "8",
            "--requests", "4", "--prompt-len", "64", "--gen", "24",
        ]
    )
    # A recurrent-state arch too (RWKV: O(1) cache, the long_500k family).
    serve_launch.main(
        [
            "--arch", "rwkv6-7b", "--reduce", "16",
            "--requests", "2", "--prompt-len", "64", "--gen", "12",
        ]
    )
