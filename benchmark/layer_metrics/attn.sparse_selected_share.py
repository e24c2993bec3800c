"""Model step, how sparse the attention stood: of the cells the window's
decode steps' indexers scored (`index_scored_cells`: a live row's whole
length in every full layer), the share their attentions then read
(`index_selected_cells`: min(that, 2,048)). 100 while every context is under
the top-k; 2,048 of 14.8k cells reads 13.8."""

from benchmark import manifest


def read(collected: dict):
    recs = manifest.load_module(
        "layer_metrics", "kernel.sparse_index_select_roofline").counted(
        collected)
    scored = sum(r["index_scored_cells"] for r in recs)
    if not scored:
        return None
    return 100.0 * sum(r["index_selected_cells"] for r in recs) / scored
