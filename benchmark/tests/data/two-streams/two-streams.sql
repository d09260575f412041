-- A two-stream query in the shape of NEXmark Query 8 (persons who opened
-- an auction in the window they registered in), for the harness's own
-- tests and no cell: a tumbling count per person.id over the person
-- events, a tumbling count per auction.seller over the auction events,
-- joined on window and id = seller. The dollar names are filled from the
-- configuration's generator settings, the traffic mix's rate and --seed.
CREATE TABLE nexmark (
  "person" BOOLEAN, "person.id" BIGINT, "auction" BOOLEAN, "auction.seller" BIGINT
) WITH (
  connector = 'nexmark',
  inter_event_micros = $inter_event_micros,
  first_event_micros = $first_event_micros,
  event_rate = $event_rate,
  seed = $seed
);
CREATE TABLE new_sellers (
  id BIGINT, registered BIGINT, opened BIGINT, ws TIMESTAMP
) WITH (connector = '$sink', type = 'sink');
INSERT INTO new_sellers
SELECT P.id, P.registered, A.opened, P.window.start
FROM (
  SELECT "person.id" AS id, count(*) AS registered,
    tumble(interval '10 seconds') AS window
  FROM nexmark WHERE "person" GROUP BY "person.id", window
) AS P
JOIN (
  SELECT "auction.seller" AS seller, count(*) AS opened,
    tumble(interval '10 seconds') AS window
  FROM nexmark WHERE "auction" GROUP BY "auction.seller", window
) AS A
ON P.window = A.window AND P.id = A.seller;
