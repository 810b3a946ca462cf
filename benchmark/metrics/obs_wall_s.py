"""Mean wall of one observation among its neighbours: the ``survey.obs``
span, first lease asked for to terminal state, over every observation of
the window. Against one beam searched alone it says what sharing the host
costs a beam."""
UNIT = "s"


def read(cell):
    if cell.telemetry is None:
        return None
    ent = cell.telemetry["spans"].get("survey.obs")
    if not ent or not ent[1]:
        return None
    return ent[0] / ent[1]
