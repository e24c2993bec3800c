"""Model step, where between short and long contexts the window stood: of
the cells the window's decode steps' attentions read, the share that were
the window layers' (`window_kv_tokens`: a live row's min(len, window) in
every window layer) and not the global layers' (`global_kv_tokens`: its
whole length in every global one), where the window is a band of pages a
slot (`models/afmoe.py`). Twelve window layers of 2,048 cells to four
global ones read 75 while every context is inside the window, 60 at 4k:
the share falls once contexts pass 2,048, which is what a window is for.
`attn.window_kv_tokens_share`'s reading, under a name of its own because
the accepted entry lists another cell."""


def read(collected: dict):
    if collected["config"].get("model_type") != "afmoe":
        return None
    recs = [r for r in collected.get("steps") or []
            if r["kind"] == "decode" and "window_kv_tokens" in r]
    window = sum(r["window_kv_tokens"] for r in recs)
    total = window + sum(r["global_kv_tokens"] for r in recs)
    if not total:
        return None
    return 100.0 * window / total
