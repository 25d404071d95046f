"""Batched serving example: prefill-by-decode + greedy generation for a
KV-cache architecture and an SSM (state-cache) architecture.

  PYTHONPATH=src python examples/serve_lm.py
"""

from repro.launch import compile_cache, serve


def main():
    compile_cache.enable()
    for arch in ("qwen2-7b", "mamba2-130m", "zamba2-2.7b"):
        serve.main(["--arch", arch, "--smoke", "--batch", "4",
                    "--prompt-len", "16", "--gen", "16",
                    "--cache-len", "64"])


if __name__ == "__main__":
    main()
