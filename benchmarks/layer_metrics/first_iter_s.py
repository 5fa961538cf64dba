"""compile (compile/): host seconds of the compile-paying call, the first
lgb.train(num_boost_round=1) to block_until_ready. Cold it compiles; warm
it loads from the cache in the checkout."""


def read(ev):
    return ev.stages.get("first_call")
