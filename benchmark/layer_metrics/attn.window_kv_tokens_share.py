"""Model step, where between short and long contexts the window stood: of
the cells the window's decode steps' attentions read, the share that were
ring cells of the window layers (`window_kv_tokens`: a live row's min(len,
window) in every window layer) and not pages of the global layers
(`global_kv_tokens`: its whole length in every global one). Five window
layers of 128 cells to two global ones read 71 at contexts of 128, 14 at
2k and 7 at 4k: the window layers' share of the attention's reads falls as
the contexts grow, which is what a window is for."""



def read(collected: dict):
    recs = [r for r in collected.get("steps") or []
            if r["kind"] == "decode" and "window_kv_tokens" in r]
    window = sum(r["window_kv_tokens"] for r in recs)
    total = window + sum(r["global_kv_tokens"] for r in recs)
    if not total:
        return None
    return 100.0 * window / total
