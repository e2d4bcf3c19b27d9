"""Share of the window's prompt tokens whose KV pages a turn took from
the sequence it forked, in %: the program's `PagedKVCache` counter
`pages_shared` over the window, times the page's tokens, over the prompt
tokens admitted."""


def read(run):
    if not run.prompt_tokens:
        return None
    return 100.0 * run.pages_shared * run.page_tokens / run.prompt_tokens
