"""Serving example: batched requests through prefill-free decode with the
serving launcher's continuous batching (slots are refilled as sequences
finish; each slot decodes at its own position).

    PYTHONPATH=src python examples/serve_lm.py
"""

from repro.launch import serve


def main():
    out = serve.run(["--arch", "smollm-360m", "--requests", "10",
                     "--slots", "4", "--max-new", "12", "--max-len", "64"])
    for rid, r in enumerate(out["requests"]):
        print(f"request {rid}: {r['tokens']}")
    print(f"served {len(out['requests'])} requests in {out['wall_s']:.1f}s "
          f"({out['decode_steps']} decode steps, 4 slots, continuous "
          "batching)")


if __name__ == "__main__":
    main()
