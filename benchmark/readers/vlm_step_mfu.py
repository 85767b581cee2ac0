"""Operations the window's captions need (tower and prefill of every
request sent, one decoder pass a streamed token at the mean context) over
the window times the chip's bf16 peak."""

from benchmark.readers.common import counts, mean_context, model_config, vlm_prompt_tokens


def read(ctx, spec):
    client = ctx["result"]["client"]
    if not ctx["peaks"] or not client.get("tokens_in_window"):
        return None
    work, cfg, prompt = counts(ctx, "vlm"), model_config(ctx, "vlm"), vlm_prompt_tokens(ctx)
    flops = (client["attempted"] * (work.image_flops(cfg) + work.prefill_flops(cfg, prompt))
             + client["tokens_in_window"] * work.decode_token_flops(cfg, mean_context(ctx)))
    return 100.0 * flops / (client["window_s"] * ctx["peaks"]["bf16_flops"])
