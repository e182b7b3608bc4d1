"""ModelRunner step (building programs): of the program keys the worker
had listed when the window opened (`dynamo_program_launches{fn, key}`: a
key is listed from its first warm-up or launch, which is when it is
built), the share that served traffic launched inside the window (the
key's count grew). 100: set-up built nothing the traffic does not use;
under `--prewarm full` the rest was built before any request was seen.
None where the program counts no launches by key."""

KEYS = "dynamo_program_launches"


def _by_key(scrape: dict) -> dict:
    return {(row.get("fn"), row.get("key")): value
            for row, value in scrape.get(KEYS, [])}


def read(ctx: dict):
    window = ctx["window"]
    before, after = _by_key(window["before"]), _by_key(window["after"])
    if not before:
        return None
    used = sum(1 for key, count in before.items()
               if after.get(key, count) > count)
    return 100.0 * used / len(before)
