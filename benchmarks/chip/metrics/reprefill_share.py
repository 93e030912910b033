"""Prompt tokens prefilled again at a later stage (the cascade's
re-prefill on escalation) over all prompt tokens prefilled in the window."""


def read(run):
    calls = run.window_calls("admit")
    total = sum(sum(c.prompt_lens) for c in calls)
    if len(run.stages) < 2 or not total:
        return None
    return sum(sum(c.prompt_lens) for c in calls if c.stage > 0) / total
