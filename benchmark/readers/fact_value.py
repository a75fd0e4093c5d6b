"""One number the driver states among its facts (``fact``), scaled
(``scale``) and, where ``over`` names a second fact, divided by it. A
program that states no such fact gives nothing to read."""


def read(args: dict, run: dict):
    facts = run["facts"]
    value = facts.get(args["fact"])
    if value is None:
        return None
    if "over" in args:
        denominator = facts.get(args["over"])
        if not denominator:
            return None
        value = value / denominator
    return args.get("scale", 1.0) * value
